"""Fleet-vectorized policy engine: K machines in one device program.

MaxMem's headline claims are statements about *populations* of colocation
scenarios — policy x seed x bandwidth sweeps — and the pre-fleet engine ran
one machine per Python process, paying full dispatch and host-sync cost
serially for every machine-epoch. This module stacks the complete per-machine
``PolicyState`` (pages, tenants, backlog, PRNG, migration queue, owner
segments) along a leading machine axis and runs the fused policy tick
``jax.vmap``-ed inside the single donated ``lax.scan`` of
``policy._multi_epoch_impl``: K machines x k epochs advance in ONE dispatch
with ONE host transfer for the stacked telemetry snapshot.

Sharding (DESIGN.md §6): when more than one XLA device is visible the
machine axis is additionally partitioned over ``jax.devices()`` with
``shard_map`` — K is padded up to a device multiple with *inert* machines
(no tenants, no backlog) whose rows are dropped from every result. No
reduction crosses a machine slice, so per-machine rows stay BIT-IDENTICAL
to the single-device vmap path and to running each machine alone
(``tests/test_fleet.py``, ``tests/test_fleet_sharded.py``). On CPU hosts
the layout is demonstrable via
``XLA_FLAGS=--xla_force_host_platform_device_count=4``.

Sweepable without recompilation (traced, batched ``PolicyParams`` leaves):
seeds, migration budgets/bandwidth/latency, sample periods, fast capacities,
targets, fairness mode. Forcing a fresh trace (static shapes): page count,
tenant-table size, queue capacity, plan size, epoch count per call.

Surface:

  * :func:`fleet_multi_epoch` — raw batched entry point on stacked pytrees.
  * :func:`fleet_multi_epoch_sharded` — the same program with the machine
    axis partitioned over a device mesh.
  * :class:`FleetManager` — facade over K :class:`CentralManager` control
    planes: register/allocate/free/telemetry stay per-machine host
    operations on the underlying managers; ``run_epochs`` stacks their
    states, runs the fleet program, and writes the advanced slices back.
    Dirty-tracking makes the stack incremental: machines untouched since
    the previous dispatch are never restacked (their advanced slices stay
    parked as lazy views), so a dispatch with no intervening control-plane
    operations performs ZERO host->device state uploads.
    ``run_epochs_async`` overlaps the telemetry fetch with host work — the
    double-buffered sweep pipeline in ``scenario.run_sweep`` builds on it.
"""
from __future__ import annotations

import atexit
import concurrent.futures
import dataclasses
import queue as queue_mod
import threading
import time
from functools import lru_cache, partial
from typing import Callable, Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, PartitionSpec

from repro.core import policy
from repro.core.manager import CentralManager, MultiEpochResult
from repro.core.types import (
    EpochStats,
    MigrationPlan,
    OwnerSegments,
    PolicyState,
    state_nbytes,
)


def fleet_multi_epoch(
    fstate,
    fparams,
    counts: Optional[jax.Array] = None,
    *,
    k: int,
    max_tenants: int,
    plan_size: int,
    exact_sampling: bool = False,
    count_clamp: int = policy.COUNT_CLAMP,
    collect_plans: bool = False,
    trim_stats: bool = False,
    compile_sentinel: bool = True,
):
    """Advance K stacked machines by ``k`` epochs in one dispatch.

    ``fstate``/``fparams`` are a ``PolicyState``/``PolicyParams`` whose
    leaves carry a leading machine axis. ``counts`` is ``None`` (consume
    each machine's recorded backlog), ``[K, P]`` (each machine replays its
    row every epoch) or ``[K, k, P]``. Returns (fstate', plans, stats,
    flagged) with leaves shaped ``[K, k, ...]`` for the per-epoch outputs.
    State buffers are donated. ``trim_stats`` drops the telemetry leaves
    the sweep record path never reads (``policy._trim_stats``).
    """
    return _jitted_fleet()(
        fstate, fparams, counts, k=k, max_tenants=max_tenants,
        plan_size=plan_size, exact_sampling=exact_sampling,
        count_clamp=count_clamp, collect_plans=collect_plans,
        trim_stats=trim_stats, compile_sentinel=compile_sentinel,
    )


def _fleet_impl(
    fstate, fparams, counts, *, k, max_tenants, plan_size, exact_sampling,
    count_clamp, collect_plans, trim_stats=False, compile_sentinel=True,
):
    step = partial(
        policy._multi_epoch_impl, k=k, max_tenants=max_tenants,
        plan_size=plan_size, exact_sampling=exact_sampling,
        count_clamp=count_clamp, collect_plans=collect_plans,
        trim_stats=trim_stats, compile_sentinel=compile_sentinel,
    )
    if counts is None:
        return jax.vmap(lambda s, p: step(s, p, None))(fstate, fparams)
    return jax.vmap(lambda s, p, c: step(s, p, c))(fstate, fparams, counts)


@lru_cache(maxsize=None)
def _machine_slicer():
    """One jitted program extracting machine ``i``'s slice from the stacked
    state: a single dispatch for the whole pytree. Eager per-leaf ``a[i]``
    indexing costs milliseconds PER LEAF on a device-sharded stack (each
    slice is its own cross-device gather); this is the difference between
    ~1 ms and ~70 ms per machine materialization on a 4-device CPU host."""
    def slice_i(tree_, i):
        return jax.tree.map(
            lambda a: jax.lax.dynamic_index_in_dim(a, i, 0, keepdims=False),
            tree_,
        )
    return jax.jit(slice_i)


@lru_cache(maxsize=None)
def _machine_updater():
    """Jitted counterpart of :func:`_machine_slicer` for the dirty-machine
    re-upload: writes one machine's state back into row ``i`` of the
    stacked pytree in a single dispatch."""
    def update_i(tree_, state_i, i):
        return jax.tree.map(
            lambda F, s: jax.lax.dynamic_update_index_in_dim(
                F, jnp.expand_dims(s, 0), i, 0
            ),
            tree_, state_i,
        )
    return jax.jit(update_i)


@lru_cache(maxsize=None)
def _jitted_fleet():
    return jax.jit(
        _fleet_impl,
        static_argnames=(
            "k", "max_tenants", "plan_size", "exact_sampling", "count_clamp",
            "collect_plans", "trim_stats", "compile_sentinel",
        ),
        donate_argnums=(0,),
    )


@lru_cache(maxsize=None)
def _jitted_sharded_fleet(
    mesh: Mesh, has_counts: bool, k: int, max_tenants: int,
    plan_size: int, exact_sampling: bool, count_clamp: int,
    collect_plans: bool, trim_stats: bool, compile_sentinel: bool = True,
):
    """One compiled shard_map program per (mesh, static-config) pair.

    Every input/output leaf carries the machine axis in front, so a single
    ``PartitionSpec('machines')`` prefix partitions the whole pytree; the
    per-shard body is the plain vmapped scan, and since no collective
    crosses a machine slice the partitioning is communication-free
    (``check_vma=False`` only disables the replication check shard_map
    would otherwise try to prove)."""
    impl = partial(
        _fleet_impl, k=k, max_tenants=max_tenants, plan_size=plan_size,
        exact_sampling=exact_sampling, count_clamp=count_clamp,
        collect_plans=collect_plans, trim_stats=trim_stats,
        compile_sentinel=compile_sentinel,
    )
    spec = PartitionSpec("machines")
    if has_counts:
        fn = jax.shard_map(
            lambda s, p, c: impl(s, p, c), mesh=mesh,
            in_specs=(spec, spec, spec), out_specs=spec, check_vma=False,
        )
    else:
        fn = jax.shard_map(
            lambda s, p: impl(s, p, None), mesh=mesh,
            in_specs=(spec, spec), out_specs=spec, check_vma=False,
        )
    return jax.jit(fn, donate_argnums=(0,))


def fleet_multi_epoch_sharded(
    fstate,
    fparams,
    counts: Optional[jax.Array] = None,
    *,
    mesh: Mesh,
    k: int,
    max_tenants: int,
    plan_size: int,
    exact_sampling: bool = False,
    count_clamp: int = policy.COUNT_CLAMP,
    collect_plans: bool = False,
    trim_stats: bool = False,
    compile_sentinel: bool = True,
):
    """:func:`fleet_multi_epoch` with the machine axis partitioned over
    ``mesh`` (axis name ``machines``). The leading dimension of every leaf
    must be divisible by the mesh size — :class:`FleetManager` guarantees
    this by padding with inert machines. Per-machine rows are bit-identical
    to the unsharded path (no reduction crosses a machine slice)."""
    fn = _jitted_sharded_fleet(
        mesh, counts is not None, k, max_tenants,
        plan_size, exact_sampling, count_clamp, collect_plans, trim_stats,
        compile_sentinel,
    )
    if counts is None:
        return fn(fstate, fparams)
    return fn(fstate, fparams, counts)


@dataclasses.dataclass
class FleetMultiEpochResult:
    """Stacked output of ``FleetManager.run_epochs``.

    All leaves are HOST numpy arrays with leading ``[K, k]`` axes — the one
    batched transfer per fleet telemetry snapshot. ``machine(m)`` views one
    machine's slice as a regular :class:`MultiEpochResult`.
    """

    stats: EpochStats  # [K, k, ...] leaves
    plans: Optional[MigrationPlan]  # [K, k, R] leaves or None
    flags: np.ndarray  # bool[K, k, T]

    @property
    def num_machines(self) -> int:
        return self.flags.shape[0]

    @property
    def num_epochs(self) -> int:
        return self.flags.shape[1]

    def machine(self, m: int) -> MultiEpochResult:
        return MultiEpochResult(
            stats=jax.tree.map(lambda a: a[m], self.stats),
            plans=None if self.plans is None else jax.tree.map(lambda a: a[m], self.plans),
            flags=self.flags[m],
        )


class DispatchError(RuntimeError):
    """The fleet dispatch worker failed or timed out. Until the device
    program launches, the fleet state is still the pre-dispatch one —
    ``FleetManager.recover_dispatch`` rolls the epoch clocks back so the
    chunk can be retried (DESIGN.md §7)."""


class _DispatchWorker:
    """The fleet's dedicated dispatch thread.

    A plain ``ThreadPoolExecutor`` has two lifecycle hazards here: its
    atexit hook JOINS the worker, so a wedged device program blocks
    interpreter exit forever, and a leaked executor keeps the process alive.
    This minimal worker is a daemon thread draining a queue of (future,
    thunk) pairs — it can never hold the interpreter hostage — and
    ``close()`` (registered with atexit, bounded join) gives orderly
    shutdown when the worker is healthy. ``FleetManager.recover_dispatch``
    simply abandons a wedged worker and starts a fresh one."""

    def __init__(self):
        self._q: "queue_mod.Queue" = queue_mod.Queue()
        self._thread = threading.Thread(
            target=self._loop, name="fleet-dispatch", daemon=True
        )
        self._closed = False
        self._thread.start()
        atexit.register(self.close)

    def _loop(self) -> None:
        while True:
            item = self._q.get()
            if item is None:
                return
            fut, fn = item
            if not fut.set_running_or_notify_cancel():
                continue
            try:
                fut.set_result(fn())
            except BaseException as e:  # propagate EVERYTHING to the future
                fut.set_exception(e)

    def submit(self, fn) -> "concurrent.futures.Future":
        if self._closed:
            raise RuntimeError("dispatch worker is closed")
        fut: concurrent.futures.Future = concurrent.futures.Future()
        self._q.put((fut, fn))
        return fut

    def close(self, timeout: float = 5.0) -> None:
        """Ask the thread to drain and exit; join at most ``timeout``
        seconds (a wedged device program is abandoned, not waited on)."""
        if self._closed:
            return
        self._closed = True
        self._q.put(None)
        if timeout > 0:
            self._thread.join(timeout)
        try:
            atexit.unregister(self.close)
        except Exception:
            pass


class _DispatchGate:
    """Decides, under a lock, whether a dispatch launches its device program
    or was abandoned first: a launched program consumes the donated state,
    so exactly one of the worker and ``recover_dispatch`` may own it."""

    def __init__(self):
        self._lock = threading.Lock()
        self._state = "pending"

    def _claim(self, to: str) -> bool:
        with self._lock:
            if self._state != "pending":
                return False
            self._state = to
            return True

    def start(self) -> bool:
        return self._claim("started")

    def abandon(self) -> bool:
        return self._claim("abandoned")


class FleetPendingResult:
    """A fleet advance running on the fleet's dispatch worker thread.

    JAX's CPU backend executes dispatches synchronously on the calling
    thread, so genuine host/device overlap needs the device program driven
    from a dedicated worker: XLA releases the GIL for the whole execution,
    and the telemetry ``device_get`` happens inside the worker too — the
    main thread records the previous chunk / prepares the next one while
    the device runs. ``result()`` joins, folds the per-machine queue
    counters exactly once, strips the inert padding rows and returns the
    host-side :class:`FleetMultiEpochResult`. (On accelerator backends the
    worker merely dispatches and blocks on the transfer — the same overlap,
    provided by the hardware queue instead.)"""

    def __init__(self, fleet: "FleetManager", future):
        self._fleet = fleet
        self._future = future
        self._result: Optional[FleetMultiEpochResult] = None

    def result(self, timeout: Optional[float] = None) -> FleetMultiEpochResult:
        """Join the dispatch. ``timeout`` (seconds) bounds the wait: on
        expiry a :class:`DispatchError` is raised and the dispatch keeps
        running — call again to keep waiting, or let the sweep supervisor
        recover and fall back to the serialized path."""
        if self._result is None:
            try:
                _fstate, (stats, flags, plans) = self._future.result(timeout)
            except concurrent.futures.TimeoutError:
                raise DispatchError(
                    f"fleet dispatch did not complete within {timeout}s"
                ) from None
            except concurrent.futures.CancelledError:
                raise
            except BaseException as e:
                # uniform fault surface: whatever the worker raised arrives
                # as a DispatchError (cause preserved) so supervisors need
                # one except clause, not a taxonomy
                raise DispatchError(f"fleet dispatch failed: {e!r}") from e
            K = len(self._fleet.machines)
            stats, flags, plans = jax.tree.map(
                lambda a: a[:K], (stats, flags, plans)
            )
            if stats.queue is not None:
                for i, m in enumerate(self._fleet.machines):
                    m._fold_queue_stats(jax.tree.map(lambda a: a[i], stats.queue))
            self._result = FleetMultiEpochResult(
                stats=stats, plans=plans, flags=flags
            )
        return self._result


class FleetManager:
    """K :class:`CentralManager` machines advancing as one device program.

    Control-plane operations (register/allocate/free/telemetry/bandwidth
    events) address the underlying managers directly — ``fleet.machines[m]``
    exposes the full per-machine surface, and any state they mutate is
    restacked on the next fleet dispatch. ``run_epochs`` is the data plane:
    stack -> one vmapped (and, with multiple devices, sharded) scan ->
    park advanced slices -> one host telemetry snapshot.

    ``devices`` selects the shard layout: ``None`` uses every local XLA
    device (sharded whenever more than one is visible), an int takes the
    first n local devices, a sequence pins explicit devices, and ``1``
    forces the single-device vmap path. K is padded up to a device multiple
    with inert machines (no tenants, no backlog — DESIGN.md §6 padding
    contract); padded rows are dropped from every result and telemetry
    read. ``pad_to`` overrides the padding multiple (testing hook).

    Dirty-tracking: after a dispatch each machine's advanced slice stays
    parked as a lazy view into the cached stacked state. Only machines
    whose control plane actually fired (any state/params mutation or a
    pending ``OwnerSegments`` rebuild) are re-uploaded before the next
    dispatch — a no-op dispatch performs zero host->device state uploads
    (``upload_stats`` counts restacked machines and segment rebuilds;
    locked by a regression test).

    Machines must agree on every SHAPE-defining knob (num_pages,
    max_tenants, queue_size, exact_sampling); traced parameters (budgets,
    bandwidth, latency, sample period, capacity, fairness) may differ per
    machine — that is the sweepable grid. Plan buffers take the fleet-wide
    maximum budget so shapes stay uniform; per-machine selections are
    unaffected (the budget itself is traced).
    """

    def __init__(
        self,
        machines: Sequence[CentralManager],
        devices=None,
        pad_to: Optional[int] = None,
    ):
        assert len(machines) > 0, "fleet needs at least one machine"
        self.machines: List[CentralManager] = list(machines)
        first = self.machines[0]
        for m in self.machines:
            assert m.num_pages == first.num_pages, "fleet machines must share num_pages"
            assert m.max_tenants == first.max_tenants, "fleet machines must share max_tenants"
            assert m.queue_size == first.queue_size, "fleet machines must share queue_size"
            assert m.exact_sampling == first.exact_sampling, (
                "fleet machines must share exact_sampling"
            )
            assert m.pool is None, (
                "pool-backed data planes are per-machine host objects; "
                "run them on a single CentralManager"
            )
        self.num_pages = first.num_pages
        self.max_tenants = first.max_tenants
        self.queue_size = first.queue_size
        self.exact_sampling = first.exact_sampling
        self.plan_size = max(m.plan_size for m in self.machines)

        if devices is None:
            devs = list(jax.devices())
        elif isinstance(devices, int):
            assert devices >= 1, "devices must be >= 1"
            local = list(jax.devices())
            assert devices <= len(local), (
                f"requested {devices} devices, only {len(local)} visible"
            )
            devs = local[:devices]
        else:
            devs = list(devices)
        self.devices = devs
        self.num_shards = len(devs)
        self.mesh = (
            Mesh(np.array(devs), ("machines",)) if len(devs) > 1 else None
        )
        K = len(self.machines)
        multiple = pad_to if pad_to is not None else self.num_shards
        assert multiple >= 1
        self.num_padded = K + (-K) % multiple
        if self.mesh is not None:
            assert self.num_padded % self.num_shards == 0, (
                f"padded machine count {self.num_padded} must divide over "
                f"{self.num_shards} devices (pad_to must be a shard multiple)"
            )
        # dirty-tracking: cached stacked state/params + per-machine params
        # identity from the moment each slice was last uploaded
        self._fstate = None
        self._fparams = None
        self._written_params: List[object] = [None] * K
        self._inert_state = None
        # the dispatch worker: one thread so device programs serialize
        # naturally while the main thread keeps the host pipeline busy
        self._worker: Optional[_DispatchWorker] = None
        self._inflight = None
        self._inflight_gate = None
        self._inflight_k = 0
        # first worker exception, noted at FAULT time by a done-callback —
        # every subsequent fleet operation raises it promptly instead of
        # deferring to the next .result() (satellite: prompt propagation)
        self._dispatch_error: Optional[BaseException] = None
        # failed machines: slot -> the real PolicyState parked at fail time
        # (the machine itself runs as an inert row until recovery)
        self._parked: Dict[int, PolicyState] = {}
        # optional worker supervision (enable_supervision): host 0 is the
        # dispatch worker; it beats when a dispatch starts and completes
        self.heartbeat = None
        # chaos hooks (tests): fail the next n dispatches / delay each one
        self._chaos_fail_n = 0
        self._chaos_delay_s = 0.0
        self.upload_stats = {
            "dispatches": 0,
            "clean_dispatches": 0,
            "restacked_machines": 0,
            "seg_rebuilds": 0,
        }

    @property
    def num_machines(self) -> int:
        return len(self.machines)

    def __len__(self) -> int:
        return len(self.machines)

    # ------------------------------------------------------------ stacking
    def _machine_dirty(self, m: CentralManager) -> bool:
        """True when the machine's row in the cached stack is stale: any
        state setter fired since the last dispatch, or an ownership change
        left a pending ``OwnerSegments`` rebuild. (Params staleness is
        tracked separately — it re-stacks the tiny params leaves only.)"""
        return m._mutated or m._segs_owner is not None

    def _make_inert_state(self) -> PolicyState:
        """A machine that computes but matters to nobody: no tenants, no
        backlog, the same static shapes as every real machine. Its rows are
        sliced off every output; its only job is making the machine count a
        shard multiple."""
        if self._inert_state is None:
            state = PolicyState.create(
                self.num_pages, self.max_tenants, seed=0,
                queue_size=self.queue_size,
            )
            self._inert_state = state._replace(
                segs=OwnerSegments.build(
                    np.full((self.num_pages,), -1, np.int32), self.max_tenants
                )
            )
        return self._inert_state

    def _check_dispatch_error(self) -> None:
        """Surface a worker fault NOW (not at the next ``.result()``). The
        error stays sticky until ``recover_dispatch`` clears it."""
        if self._dispatch_error is not None:
            raise DispatchError(
                f"fleet dispatch worker failed: {self._dispatch_error!r}"
            ) from self._dispatch_error

    def _join(self):
        """Adopt the in-flight dispatch's advanced stacked state (if any).
        This is the pipeline's sync point: it blocks until the worker's
        device program — and its telemetry transfer — completed."""
        self._check_dispatch_error()
        if self._inflight is not None:
            try:
                fstate, _host = self._inflight.result()
            except concurrent.futures.CancelledError:
                raise
            except BaseException as e:
                raise DispatchError(f"fleet dispatch failed: {e!r}") from e
            self._fstate = fstate
            self._inflight = None
        return self._fstate

    def _assemble(self) -> None:
        """Bring the cached stacked state/params up to date, uploading only
        the machines whose control plane fired since the last dispatch."""
        self._join()
        K = len(self.machines)
        pad = self.num_padded - K
        dirty = [
            i for i, m in enumerate(self.machines)
            if self._fstate is None or self._machine_dirty(m)
        ]
        for i in dirty:
            if self.machines[i]._segs_owner is not None:
                self.upload_stats["seg_rebuilds"] += 1
            self.machines[i]._ensure_segs()
        if self._fstate is None or len(dirty) == K:
            states = [m._state for m in self.machines]
            if pad:
                states = states + [self._make_inert_state()] * pad
            self._fstate = jax.tree.map(lambda *xs: jnp.stack(xs), *states)
            self.upload_stats["restacked_machines"] += K
        elif dirty:
            for i in dirty:
                self._fstate = _machine_updater()(
                    self._fstate, self.machines[i]._state, i
                )
            self.upload_stats["restacked_machines"] += len(dirty)
        params_dirty = self._fparams is None or any(
            m.params is not self._written_params[i]
            for i, m in enumerate(self.machines)
        )
        if params_dirty:
            plist = [m.params for m in self.machines]
            if pad:
                plist = plist + [self.machines[0].params] * pad
            self._fparams = jax.tree.map(
                lambda *xs: jnp.stack([jnp.asarray(x) for x in xs]), *plist
            )
        if not dirty and not params_dirty:
            self.upload_stats["clean_dispatches"] += 1

    def _park_slices(self) -> None:
        """Point every machine's state at its (lazy) slice of the advanced
        stack; nothing materializes — and the in-flight dispatch is not
        even joined — until a control-plane or telemetry path actually
        reads a machine."""

        def slicer(i: int) -> Callable[[], PolicyState]:
            return lambda: _machine_slicer()(self._join(), i)

        for i, m in enumerate(self.machines):
            m._set_fleet_state(slicer(i))
            self._written_params[i] = m.params

    # ------------------------------------------------------------ dispatch
    def run_epochs_async(
        self,
        k: int,
        counts: Optional[np.ndarray] = None,
        collect_plans: bool = False,
        trim_stats: bool = False,
        inline: bool = False,
    ) -> FleetPendingResult:
        """Dispatch ``k`` epochs for every machine and return immediately.

        The returned handle's ``result()`` materializes the telemetry; in
        the meantime the host can record the previous chunk, prepare the
        next one, or fire control-plane events — the double-buffered sweep
        pipeline (``scenario.run_sweep``) lives on exactly this overlap.
        ``inline=True`` runs the same program synchronously on the calling
        thread and returns a pre-resolved handle — the serialized fallback
        the sweep supervisor degrades to when the worker misbehaves.
        """
        self._check_dispatch_error()
        K = len(self.machines)
        pad = self.num_padded - K
        self._assemble()
        cn = None
        if counts is not None:
            cn = np.asarray(counts)
            assert cn.ndim in (2, 3) and cn.shape[0] == K, (
                f"counts must be [K, P] or [K, k, P] with K={K}, got {cn.shape}"
            )
            if pad:
                cn = np.concatenate(
                    [cn, np.zeros((pad,) + cn.shape[1:], cn.dtype)], axis=0
                )
        kw = dict(
            k=k, max_tenants=self.max_tenants, plan_size=self.plan_size,
            exact_sampling=self.exact_sampling, collect_plans=collect_plans,
            trim_stats=trim_stats,
        )
        mesh = self.mesh
        fstate_in, fparams_in = self._fstate, self._fparams
        hb = self.heartbeat
        chaos_fail = self._chaos_fail_n > 0
        if chaos_fail:
            self._chaos_fail_n -= 1
        chaos_delay = self._chaos_delay_s

        gate = _DispatchGate()

        def work():
            if hb is not None:
                hb.beat(0)
            if chaos_delay:
                time.sleep(chaos_delay)
            if chaos_fail:
                raise RuntimeError("injected dispatch failure (chaos hook)")
            if not gate.start():
                # abandoned by recover_dispatch: the retry owns the stack
                raise concurrent.futures.CancelledError()
            c = None
            if cn is not None:
                # host->device upload of the workload happens in the worker
                # too — off the main thread's critical path
                c = jnp.asarray(cn.astype(np.uint32, copy=False))
            if mesh is not None:
                fstate, plans, stats, flagged = fleet_multi_epoch_sharded(
                    fstate_in, fparams_in, c, mesh=mesh, **kw
                )
            else:
                fstate, plans, stats, flagged = fleet_multi_epoch(
                    fstate_in, fparams_in, c, **kw
                )
            host = jax.device_get(
                (stats, flagged, plans if collect_plans else None)
            )
            if hb is not None:
                hb.beat(0)
            return fstate, host

        if inline:
            # serialized fallback: run on the calling thread; failures raise
            # here directly and leave the pre-dispatch state intact
            fut: concurrent.futures.Future = concurrent.futures.Future()
            fut.set_result(work())
            self._inflight = fut
        else:
            if self._worker is None:
                self._worker = _DispatchWorker()
            self._inflight = self._worker.submit(work)
            self._inflight.add_done_callback(self._note_dispatch_outcome)
        self._inflight_gate = gate
        self._inflight_k = k
        self._park_slices()
        for m in self.machines:
            m.epoch_index += k
            m._snap = None
        self.upload_stats["dispatches"] += 1
        return FleetPendingResult(self, self._inflight)

    def _note_dispatch_outcome(self, fut) -> None:
        """Done-callback on the worker future: record the first failure at
        FAULT time so the main thread learns about it at its next fleet
        call, not only when it finally asks for the result."""
        if fut.cancelled() or getattr(fut, "_fleet_abandoned", False):
            return
        exc = fut.exception()
        if exc is not None and self._dispatch_error is None:
            self._dispatch_error = exc

    def recover_dispatch(self) -> None:
        """Reset after a failed (or wedged) dispatch so the chunk can be
        retried: drop the in-flight future, clear the sticky error, roll the
        per-machine epoch clocks back by the dispatched k, and abandon the
        worker thread — a fresh daemon is created on the next dispatch. A
        supervised fleet also gets a fresh ``HeartbeatTracker`` (the old one
        latched the worker dead).

        The device program donates the stacked state, so a rollback is only
        possible while that program has not been launched: the abandoned
        worker is barred from launching it, and a dispatch that already
        launched (finished or not) raises :class:`DispatchError` here
        instead of retrying on consumed buffers.
        """
        if self._inflight is not None:
            if not self._inflight_gate.abandon():
                raise DispatchError(
                    "the dispatch already launched its device program, which "
                    "consumed the donated fleet state: no rollback"
                )
            # flag before cancel: an abandoned-but-running future resolves
            # later and must not re-arm the sticky error we just cleared
            self._inflight._fleet_abandoned = True
            self._inflight.cancel()
            self._inflight = None
            for m in self.machines:
                m.epoch_index -= self._inflight_k
                m._snap = None
            # the parked lazy slices point at _join(); with the in-flight
            # future dropped they resolve to the pre-dispatch stack rows
        self._inflight_k = 0
        self._dispatch_error = None
        if self._worker is not None:
            self._worker.close(timeout=0.0)  # abandon, never block on a wedge
            self._worker = None
        if self.heartbeat is not None:
            self.enable_supervision(
                timeout=self.heartbeat.timeout, clock=self.heartbeat.clock
            )

    # ---------------------------------------------------------- supervision
    def enable_supervision(self, timeout: float = 60.0, clock=None) -> None:
        """Watch the dispatch worker with the seed's ``HeartbeatTracker``
        (host id 0 = the worker; it beats at dispatch start and completion).
        ``check_worker()`` returning a non-empty list means the worker has
        been silent longer than ``timeout`` — the sweep supervisor then
        recovers and falls back to the serialized path. ``clock`` is
        injectable for tests (fake time)."""
        from repro.runtime.fault_tolerance import HeartbeatTracker

        kw = {} if clock is None else {"clock": clock}
        self.heartbeat = HeartbeatTracker([0], timeout=timeout, **kw)

    def check_worker(self) -> List[int]:
        """Newly-dead host ids from the supervision tracker ([] when
        healthy or supervision is off)."""
        if self.heartbeat is None:
            return []
        return self.heartbeat.check()

    # --------------------------------------------------------- machine faults
    @property
    def failed_machines(self) -> List[int]:
        return sorted(self._parked)

    def fail_machine(self, i: int) -> None:
        """Drop machine ``i`` mid-sweep (the MachineFail scenario event).

        Its real ``PolicyState`` is parked host-side and the machine runs as
        an inert row — same static shapes, no tenants, no backlog — so the
        fleet program's geometry never changes. The PRNG stream and queue
        are frozen exactly where the failure left them; ``recover_machine``
        restores them bit-identically. The machine's ``epoch_index`` keeps
        advancing while parked: it is the fleet's wall clock, and the down
        window is real elapsed time (the simulator records it as zero
        throughput)."""
        if i in self._parked:
            raise ValueError(f"machine {i} is already failed")
        m = self.machines[i]
        m._ensure_segs()  # park a self-consistent state (segs current)
        self._parked[i] = m._state  # materializes the lazy slice
        m._state = self._make_inert_state()
        m._snap = None

    def recover_machine(self, i: int) -> None:
        """Restore machine ``i``'s parked state (the MachineRecover event).
        The state setter marks the row dirty, so the next dispatch uploads
        the real state back into the stack."""
        if i not in self._parked:
            raise ValueError(f"machine {i} is not failed")
        m = self.machines[i]
        m._state = self._parked.pop(i)
        m._snap = None

    def run_epochs(
        self,
        k: int,
        counts: Optional[np.ndarray] = None,
        collect_plans: bool = False,
        trim_stats: bool = False,
    ) -> FleetMultiEpochResult:
        """Advance every machine by ``k`` epochs in ONE device dispatch.

        ``counts``: None (consume each machine's recorded backlog), ``[K,
        P]`` (per-machine steady-state replay) or ``[K, k, P]``. Per-machine
        telemetry is bit-identical to ``CentralManager.run_epochs`` on each
        machine alone.
        """
        return self.run_epochs_async(
            k, counts=counts, collect_plans=collect_plans,
            trim_stats=trim_stats,
        ).result()

    # ----------------------------------------------------------- telemetry
    def live_bytes(self) -> int:
        """Array bytes of the stacked fleet state (padded machine rows
        included — padding occupies real device memory). The scale bench
        records this per geometry: every per-page leaf scales as K x P, so
        the packed i16 owner / i8 queue-heat layouts shrink exactly the
        term that dominates at a million pages."""
        self._assemble()
        return state_nbytes(self._fstate)

    def stacked_placement(self) -> Tuple[np.ndarray, np.ndarray]:
        """(tier[K, P], owner[K, P]) for every machine in ONE batched
        device->host transfer, seeding each manager's telemetry snapshot
        cache — replaces K per-machine ``device_get`` round trips on the
        sweep pipeline's critical path. Falls back to per-machine snapshots
        when a machine mutated since the last dispatch (its row in the
        cached stack is stale)."""
        K = len(self.machines)
        self._join()
        clean = self._fstate is not None and not any(
            m._mutated for m in self.machines
        )
        if clean:
            tier, owner = jax.device_get(
                (self._fstate.pages.tier, self._fstate.pages.owner)
            )
            tier, owner = tier[:K], owner[:K]
            for i, m in enumerate(self.machines):
                m._snap = {"tier": tier[i], "owner": owner[i]}
            return tier, owner
        tier = np.stack([m.tiers() for m in self.machines])
        owner = np.stack([m.owners() for m in self.machines])
        return tier, owner
