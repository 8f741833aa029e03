"""BohmEngine: the two-phase batch pipeline (CC phase -> barrier -> exec).

One jitted call runs:   plan -> wavefront execute -> watermark commit.
The CC phase can run record-partitioned over a mesh axis (``cc_shards``),
reproducing the paper's intra-transaction parallelism; the execution phase
is transaction-partitioned (the wavefront vector step IS the union of all
execution threads' work for a wave). The commit/GC step and the snapshot
read path run against the record-partitioned version store
(``repro.store.sharded``) — rings, watermark GC and ``mvcc_resolve``
visibility all per shard, with ``n_shards == 1`` bit-identical to the
plain single ring.

The paper overlaps CC of batch b+1 with execution of batch b (two thread
pools). The step is a first-class PHASE GRAPH: ``plan_phase`` (CC),
``exec_phase`` (wavefront) and ``commit_phase`` (barrier + ring commit)
are separate jits, and ``run_batch`` is a thin composition of the three.
The conflict-aware scheduler (``repro.service.TxnService``) exploits the
split three ways: CC(b+1) dispatches while exec(b) is in flight (no store
dependency), exec(b+1) dispatches BEFORE commit(b) when the two batches'
record footprints are disjoint (exec reads only ``store.base`` rows in
its read-set, none of which the deferred commit writes), and several
admitted batches with pairwise-disjoint footprints merge into one CC
epoch (one plan + one wavefront + one commit over the concatenated
batch). ``_bohm_step`` keeps the fully fused single-dispatch variant for
benchmarks that time the monolithic step.

Snapshot reads (paper §4.1.3 / Figs 9-10): because the commit step retains
versions in cross-batch rings (see repro/store/), read-only transactions
can run against OLDER snapshots while update batches stream through —
``begin_snapshot`` pins a timestamp (holding the GC watermark down),
``snapshot_read`` / ``run_readonly_batch`` resolve visibility through the
Pallas ``mvcc_resolve`` kernel, and ``release_snapshot`` lets the
watermark advance again. Read-only transactions never enter the CC phase
and never write shared state — the paper's zero-bookkeeping read path.
"""
from __future__ import annotations

import dataclasses
import time
from typing import Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp

import numpy as np

from repro.core import plan as plan_mod
from repro.core.execute import (Store, commit, execute_plan, init_store,
                                store_from_base)
from repro.core.plan import MAX_BATCH_TXNS, Plan, cc_plan
from repro.core.txn import TxnBatch, Workload
from repro.obs import MetricsRegistry, PhaseTracer, engine_health
from repro.obs.lifecycle import NULL_AUDIT, LifecycleAuditor
from repro.runtime import jit_named
from repro.store import (INF_TS, decay_pressure, from_global,
                         gather_windows_sharded, gc_sharded,
                         gc_sharded_audited, reassign_k, reassign_stats,
                         resolve_sharded, store_occupancy, to_global)


@dataclasses.dataclass(frozen=True)
class SnapshotHandle:
    """An active reader registration; holds the GC watermark at <= ts.
    ``t_wall`` (monotonic registration time) feeds the oldest-pin-age
    health gauge; it never participates in equality/ordering."""
    sid: int
    ts: int
    t_wall: float = dataclasses.field(default=0.0, compare=False)


class BohmEngine:
    def __init__(self, num_records: int, workload: Workload,
                 mesh=None, cc_axis: str = "cc", ring_slots: int = 4,
                 n_shards: Optional[int] = None,
                 spill_buckets: Optional[int] = None,
                 spill_slots: int = 8,
                 adaptive_k: bool = False, k_min: int = 1,
                 k_max: Optional[int] = None,
                 paged: bool = False, page_slots: int = 4,
                 pages_per_shard: Optional[int] = None,
                 pressure_decay: Optional[float] = None,
                 k_quantum: Optional[int] = None,
                 registry: Optional[MetricsRegistry] = None,
                 tracer: Optional[PhaseTracer] = None,
                 auditor: Optional[LifecycleAuditor] = None):
        """``spill_slots`` > 0 (default 8) attaches a per-shard spill pool
        of ``spill_buckets`` x ``spill_slots`` slots (default: one bucket
        per 4 local records) — live K-ring evictions land there instead
        of being dropped, and snapshot reads fall through primary ->
        spill; ``spill_slots=0`` restores the bare drop-oldest ring.
        ``adaptive_k=True`` allocates rings at ``k_max`` physical slots
        (default 2x ``ring_slots``) but caps every record at ``ring_slots``
        effective slots, then lets ``gc_sweep`` move capacity from cold
        records to hot ones within the fixed budget R x ``ring_slots``
        (see repro/store/policy.py).

        ``paged=True`` swaps the dense [R, k_max] rings for the paged
        slab (``repro.store.pages``): ``pages_per_shard`` pages of
        ``page_slots`` slots per shard (default: ``ceil(ring_slots /
        page_slots)`` pages per record, so every record can physically
        reach its initial capacity), per-record page tables, and
        reads through the page table into ``mvcc_resolve``. Logical
        semantics are the dense ring's; physically a cold record holds
        one page instead of ``k_max`` slots and capacity moves at page
        granularity (``reassign_k`` quantum = ``page_slots``, so
        adaptive paged stores require ``ring_slots`` and ``k_max`` to be
        page multiples). ``storage_stats()`` reports the footprint.

        ``pressure_decay`` (sweeps, optional) applies an EWMA half-life
        to the adaptive-K pressure input so a migrated hot set's old
        records cool to donors instead of holding their peak grant
        forever; None keeps the raw cumulative histogram. ``k_quantum``
        overrides the policy quantum (default: ``page_slots`` when
        paged, else 1) — the dense twin of a paged store in equivalence
        tests runs the same page-granular policy.

        ``registry`` (optional shared ``repro.obs.MetricsRegistry``)
        receives every engine counter under ``engine/`` names — hot-path
        accumulation is device-side (lazy adds on the jitted phases'
        metric outputs, no host sync); ``registry.snapshot()`` is the one
        transfer point. Default: a private registry, so the legacy stats
        surfaces (``overflow_stats`` / ``spill_stats`` /
        ``storage_stats``) work stand-alone. ``tracer`` (optional
        ``repro.obs.PhaseTracer``) wraps plan/exec/commit, the read-only
        resolve, ``gc_sweep`` and ``reassign_k`` in unfenced host spans
        (``engine/*``); the default tracer records nothing and only
        annotates the profiler's trace, and no tracer adds a host sync.
        ``auditor`` (optional ``repro.obs.LifecycleAuditor``) turns on the
        version-lifecycle audit: the commit jit emits fixed-shape ``audit_*``
        transition arrays, ``gc_sweep`` runs the audited sweep (delay
        distribution + pin certification) and harvests the bounded host
        audit ring — still zero fences on or off (the audit arrays ride
        the existing dispatches; the one ``jax.device_get`` happens at
        sweep/snapshot boundaries)."""
        if num_records > (1 << 20):
            raise ValueError("composite uint32 keys require R <= 2^20")
        self.num_records = num_records
        self.workload = workload
        self.mesh = mesh
        self.cc_axis = cc_axis
        self.ring_slots = ring_slots
        self.adaptive_k = bool(adaptive_k)
        self.k_min = int(k_min)
        self.k_max = int(k_max if k_max is not None
                         else (2 * ring_slots if adaptive_k
                               else ring_slots))
        if self.k_max < ring_slots:
            raise ValueError("k_max must be >= ring_slots")
        if not 1 <= self.k_min <= ring_slots:
            raise ValueError("k_min must be in [1, ring_slots] (k_eff "
                             "starts at ring_slots)")
        self.paged = bool(paged)
        self.page_slots = int(page_slots) if self.paged else 0
        self.k_quantum = int(k_quantum) if k_quantum is not None else (
            self.page_slots if self.paged else 1)
        if self.adaptive_k and self.k_quantum > 1:
            if ring_slots % self.k_quantum or self.k_max % self.k_quantum:
                raise ValueError(
                    "page-quantized adaptive K requires ring_slots and "
                    "k_max to be multiples of the quantum (page_slots)")
        self.pressure_decay = (float(pressure_decay)
                               if pressure_decay is not None else None)
        if n_shards is None:
            n_shards = mesh.shape[cc_axis] if (
                mesh is not None and cc_axis in mesh.shape) else 1
        self.n_shards = int(n_shards)
        records_local = -(-num_records // self.n_shards)
        self.pages_per_shard = 0
        if self.paged:
            # default: every record can physically reach its initial
            # k_eff — ceil(ring_slots / S) pages each (for page-multiple
            # capacities this IS the slot budget in pages); callers
            # shrink it explicitly to trade found-rate for memory
            self.pages_per_shard = int(
                pages_per_shard if pages_per_shard is not None
                else records_local * -(-ring_slots // self.page_slots))
        self.spill_slots = int(spill_slots)
        self.spill_buckets = int(spill_buckets if spill_buckets is not None
                                 else max(1, records_local // 4)
                                 ) if self.spill_slots > 0 else 0
        self.store = init_store(num_records, workload.payload_words,
                                ring_slots=self.k_max,
                                n_shards=self.n_shards,
                                spill_buckets=self.spill_buckets,
                                spill_slots=self.spill_slots,
                                k_init=ring_slots, paged=self.paged,
                                page_slots=self.page_slots or 4,
                                pages_per_shard=self.pages_per_shard
                                or None, mesh=mesh, axis=cc_axis)
        self._ts_next = 1                  # host mirror of store.ts_counter
        self._snapshots: Dict[int, SnapshotHandle] = {}
        self._next_sid = 0
        self.metrics = registry if registry is not None \
            else MetricsRegistry()
        self.tracer = tracer if tracer is not None \
            else PhaseTracer(enabled=False, annotate=True)
        self.auditor = auditor if auditor is not None else NULL_AUDIT
        self._declare_metrics()
        # adaptive-K hysteresis: a record donates capacity only after
        # sitting idle across two consecutive policy passes
        self._stable_idle = np.zeros((num_records,), bool)
        self._commits_since_sweep = 0
        # EWMA pressure state (pressure_decay): decayed accumulator +
        # the cumulative histogram at the last sweep (for deltas)
        self._pressure_ewma = np.zeros((num_records,), np.float64)
        self._overflow_at_sweep = np.zeros((num_records,), np.int64)
        # each phase's program carries its function's name on the device
        # (``jit_commit_phase``, ...), which the profiler's trace shows
        self._step = jit_named(_bohm_step, workload=workload, mesh=mesh,
                               cc_axis=cc_axis)
        self._plan = jit_named(plan_phase, mesh=mesh, cc_axis=cc_axis)
        self._exec = jit_named(exec_phase, workload=workload)
        # the commit takes the store by donation and updates it in place:
        # every earlier dispatch that reads the store is already enqueued,
        # and no value handed out of the engine is a store leaf
        self._commit = jit_named(commit_phase, donate_argnums=(2,),
                                 mesh=mesh, cc_axis=cc_axis,
                                 with_audit=self.auditor.enabled)
        self._gc = jax.jit(gc_sharded)
        self._gc_audit = jit_named(gc_sharded_audited,
                                   event_cap=self.auditor.gc_event_cap)
        self._gather = jax.jit(gather_windows_sharded)
        self._readonly = jit_named(_readonly_resolve, mesh=mesh,
                                   cc_axis=cc_axis)

    _SPILL_KEYS = ("spill_admitted", "spill_dropped",
                   "spill_overwrote_pinned")
    # versions committed, and the busiest shard's, summed over epochs
    _SHARD_KEYS = ("shard_writes", "shard_writes_max")

    def _declare_metrics(self) -> None:
        """(Re)declare the engine's device counters on the registry —
        run at init and at ``reset_store`` (the counters' lifecycle
        follows the store's). All under ``engine/`` names; the legacy
        stats surfaces read through them unchanged."""
        m = self.metrics
        k_eff = self.store.versions.k_eff
        scalar = jnp.zeros((), jnp.int32)
        m.declare("engine/ring_overwrote_rec", k_eff)
        m.declare("engine/ring_overwrote_dead_rec", k_eff)
        for name in ("ring_overwrote_live", "ring_overwrote_dead",
                     "paged_alloc_failed", "aborts", "waves",
                     *self._SPILL_KEYS, *self._SHARD_KEYS):
            m.declare(f"engine/{name}", scalar)
        m.set("engine/commits", 0)
        m.set("engine/txns_committed", 0)
        if self.auditor.enabled:
            # lifecycle counters share the store's lifecycle too
            self.auditor.bind_engine(self)

    # -- update path -------------------------------------------------------
    def run_batch(self, batch: TxnBatch
                  ) -> Tuple[jax.Array, Dict[str, jax.Array]]:
        """One batch through the phase graph: plan -> exec -> commit,
        three jitted dispatches (the scheduler in ``repro.service`` calls
        the same three jits with its own interleaving; ``_step`` is the
        fused single-dispatch twin used by throughput benchmarks)."""
        if batch.size > MAX_BATCH_TXNS:
            raise ValueError("composite uint32 keys require T <= 2^12")
        tr = self.tracer
        wm = jnp.asarray(self.watermark(), jnp.int32)
        pins = self.pin_array()
        with tr.span("engine/plan", txns=batch.size):
            plan = self._plan(batch, self.store.ts_counter)
        with tr.span("engine/exec", txns=batch.size):
            w_data, read_vals, exec_metrics = self._exec(plan, batch,
                                                         self.store)
        with tr.span("engine/commit", txns=batch.size):
            self.store, ring_metrics = self._commit(
                plan, batch, self.store, w_data, wm, None, pins)
        metrics = dict(exec_metrics, **ring_metrics)
        self.claim_ts_window(batch.size)
        self.record_commit_metrics(metrics, n_txns=batch.size)
        return read_vals, metrics

    def run_stream(self, batches) -> Dict[str, jax.Array]:
        """Pipelined batches (paper §4.1.4 / §4.2): the CC phase of batch
        b+1 overlaps the execution of batch b. JAX's async dispatch gives
        the overlap directly — each ``run_batch`` enqueues its three
        phase jits without blocking, so while the device executes batch
        b's wavefront the host is already tracing/enqueuing b+1's plan;
        the only synchronisation is the data dependency on the committed
        store (the paper's batch barrier). Returns the metrics of the
        final batch.

        ``repro.service.TxnService`` is the full scheduler built on this
        overlap: admission queue, explicitly split plan/exec dispatch,
        submit/poll tickets, snapshot-aware watermarks."""
        metrics = None
        for batch in batches:
            # no block_until_ready: dispatch and move on
            _, metrics = self.run_batch(batch)
        jax.block_until_ready(self.store.base)
        return metrics

    def snapshot(self) -> jax.Array:
        """The committed head state [R, D], as a copy: the store's own
        arrays are donated to the next commit."""
        if self.auditor.enabled:
            self.auditor.harvest()
        return jnp.copy(self.store.base)

    def reset_store(self, base: jax.Array,
                    base_ts: Optional[jax.Array] = None) -> None:
        """Reinitialise committed state (head cache + rings + spill) from
        ``base`` (copied: the caller's array stays its own). The old store
        is let go before the new one is built, so the two are never on
        the devices together."""
        self.store = None
        self.store = store_from_base(base, base_ts, self.k_max,
                                     self.n_shards,
                                     spill_buckets=self.spill_buckets,
                                     spill_slots=self.spill_slots,
                                     k_init=self.ring_slots,
                                     paged=self.paged,
                                     page_slots=self.page_slots or 4,
                                     pages_per_shard=self.pages_per_shard
                                     or None, mesh=self.mesh,
                                     axis=self.cc_axis)
        self._ts_next = 1
        self._snapshots.clear()
        self._declare_metrics()
        self._stable_idle = np.zeros((self.num_records,), bool)
        self._commits_since_sweep = 0
        self._pressure_ewma = np.zeros((self.num_records,), np.float64)
        self._overflow_at_sweep = np.zeros((self.num_records,), np.int64)

    # -- snapshot-read path (zero CC bookkeeping) --------------------------
    def current_ts(self) -> int:
        """Snapshot timestamp that sees exactly the committed transactions:
        the last assigned global ts. (A version is visible at ts when
        begin <= ts < end, so pinning the NEXT unassigned ts would leak the
        following batch's first transaction into the snapshot.)"""
        return self._ts_next - 1

    def watermark(self) -> int:
        """Low watermark: min active reader snapshot ts. With no readers it
        is the next unassigned ts — no future reader can pin below it, so
        everything superseded up to now is reclaimable (the seed's
        Condition-3 barrier GC as the degenerate case)."""
        return min([s.ts for s in self._snapshots.values()]
                   + [self._ts_next])

    def claim_ts_window(self, n_txns: int) -> Tuple[int, int]:
        """Reserve the next ``n_txns`` global timestamps and return the
        half-open window ``(lo, lo + n_txns)``. This is Bohm's layered ts
        assignment as an explicit API: the scheduler claims windows in
        DISPATCH order (which, under out-of-order admission, may differ
        from submission order) and threads them through
        ``commit(..., ts_window=)`` so the store's timestamp accounting
        follows the dispatched schedule. Claim only after capturing this
        epoch's ``watermark()``/``pin_array()`` — the watermark reads the
        un-advanced mirror."""
        lo = self._ts_next
        self._ts_next += n_txns
        return lo, lo + n_txns

    def pin_array(self) -> jax.Array:
        """Registered snapshot pin timestamps as a device vector, sorted
        and INF_TS-padded to a power-of-two length (a pad pin never stabs
        any closed version). This is the commit path's input for the
        pin-precise live/dead eviction split and the spill tier's
        admission/victim decisions."""
        pins = sorted(s.ts for s in self._snapshots.values())
        n = 1
        while n < len(pins):
            n *= 2
        pins = pins + [int(INF_TS)] * (n - len(pins))
        return jnp.asarray(pins, jnp.int32)

    def gc_sweep(self) -> int:
        """Standalone precise GC at the current watermark — reclamation is
        watermark-driven, not barrier-driven, so it can run at any point
        between batches. A merged CC epoch (``repro.service`` conflict-
        aware admission) commits several batches through ONE barrier and
        thereby defers the intermediate sweeps a batch-per-barrier
        schedule would have run; since those sweeps only touch versions
        invisible to every legal reader, a sweep at the current watermark
        restores the canonical ring state (bit-identical to the sequential
        schedule's swept state — property-tested). The sweep covers the
        spill pools too: once every pin at or below a spilled version's
        window releases, the slot drains back to free.

        With ``adaptive_k`` the sweep boundary is also the policy
        boundary: the accumulated live-eviction histogram drives one
        ``reassign_k`` pass (hot records grow toward ``k_max``, pressure-
        free ones shrink toward ``k_min``, total budget fixed). The pass
        is a fixpoint of the pressure vector, so consecutive sweeps with
        no commits in between leave the store byte-identical.

        Returns the number of versions reclaimed (rings + spill);
        synchronises on it."""
        wm_host = self.watermark()
        with self.tracer.span("engine/gc_sweep", watermark=wm_host) as sp:
            wm = jnp.asarray(wm_host, jnp.int32)
            if self.auditor.enabled:
                versions, evicted, gc_audit = self._gc_audit(
                    self.store.versions, wm, self.pin_array())
                self.auditor.on_gc(gc_audit, wm_host)
            else:
                versions, evicted = self._gc(self.store.versions, wm)
            # the policy runs only when commits landed since the last
            # sweep: a sweep is pure reclamation, so with nothing new
            # committed the pressure/occupancy inputs are unchanged and
            # rerunning the pass (or advancing the idle streak) would
            # break byte-idempotence
            if self.adaptive_k and self._commits_since_sweep > 0:
                versions = self._run_policy(versions)
            self.store = dataclasses.replace(self.store,
                                             versions=versions)
            evicted = int(evicted)
            sp.note(reclaimed=evicted)
        self.metrics.inc("engine/gc_sweeps")
        self.metrics.inc("engine/gc_reclaimed", evicted)
        # sweep boundary = audit-harvest boundary (one device_get; the
        # hot path between sweeps stays fence-free)
        if self.auditor.enabled:
            self.auditor.harvest()
        return evicted

    def _run_policy(self, versions):
        """One adaptive-K ``reassign_k`` pass at the sweep boundary
        (host-side; its own trace span — the policy is the sweep's
        expensive part and worth separate attribution)."""
        with self.tracer.span("engine/reassign_k") as sp:
            cumulative = np.asarray(
                to_global(versions,
                          self.metrics.peek("engine/ring_overwrote_rec")),
                np.int64)
            if self.pressure_decay is None:
                pressure = cumulative
            else:
                # EWMA over per-sweep deltas: a cooled record's pressure
                # halves every ``pressure_decay`` sweeps and eventually
                # truncates to zero — it becomes a donor and its
                # capacity (pages) flows to the new hot set
                self._pressure_ewma = decay_pressure(
                    self._pressure_ewma,
                    cumulative - self._overflow_at_sweep,
                    self.pressure_decay)
                self._overflow_at_sweep = cumulative
                pressure = self._pressure_ewma
            k_glob = np.asarray(to_global(versions, versions.k_eff))
            occ = np.asarray(store_occupancy(versions))
            idle = occ <= 1
            new_k = reassign_k(pressure, k_glob, k_min=self.k_min,
                               k_max=self.k_max, k_base=self.ring_slots,
                               occupancy=occ,
                               stable_idle=idle & self._stable_idle,
                               budget=self.num_records * self.ring_slots,
                               quantum=self.k_quantum)
            self._stable_idle = idle
            self._commits_since_sweep = 0
            moved = reassign_stats(k_glob, new_k, self.k_quantum)
            sp.note(**moved)
            self.metrics.inc("engine/k_slots_granted",
                             moved["slots_granted"])
            self.metrics.inc("engine/k_slots_reclaimed",
                             moved["slots_reclaimed"])
            k_sh = from_global(versions, jnp.asarray(new_k),
                               pad_value=self.k_min)
            # insertion cursors must stay inside the (possibly shrunk)
            # effective window; grown records keep their cursor as-is
            if versions.rings is not None:
                prim = dataclasses.replace(
                    versions.rings, head=versions.rings.head % k_sh)
                versions = dataclasses.replace(versions, rings=prim,
                                               k_eff=k_sh)
            else:
                prim = dataclasses.replace(
                    versions.pages, head=versions.pages.head % k_sh)
                versions = dataclasses.replace(versions, pages=prim,
                                               k_eff=k_sh)
        return versions

    def k_by_record(self) -> jax.Array:
        """[R] effective primary-ring capacity per record (adaptive K)."""
        return to_global(self.store.versions, self.store.versions.k_eff)

    def begin_snapshot(self, ts: Optional[int] = None) -> SnapshotHandle:
        """Register a reader at ``ts`` (default: now, i.e. a snapshot of
        all committed transactions). Versions visible at or after the
        lowest registered ts survive every subsequent batch barrier until
        the reader is released."""
        handle = SnapshotHandle(self._next_sid,
                                self.current_ts() if ts is None
                                else int(ts),
                                t_wall=time.monotonic())
        self._next_sid += 1
        self._snapshots[handle.sid] = handle
        return handle

    def release_snapshot(self, handle: SnapshotHandle) -> None:
        self._snapshots.pop(handle.sid, None)

    def snapshot_windows(self, records) -> Tuple[jax.Array, jax.Array,
                                                 jax.Array]:
        """Gathered (begin, end, payload) candidate windows per record —
        the ``mvcc_resolve`` kernel's input layout, gathered from each
        record's owning shard."""
        return self._gather(self.store.versions,
                            jnp.asarray(records, jnp.int32))

    def snapshot_read(self, records, ts: Optional[int] = None
                      ) -> Tuple[jax.Array, jax.Array]:
        """Resolve ``records`` [B] at snapshot ``ts`` through the Pallas
        kernel, per shard, falling through primary ring -> spill pool.
        Returns (vals [B, D], found [B]); found=False means the visible
        version was never written, or was evicted while unpinned (dead),
        or was dropped by a saturated spill pool — never a stale
        payload."""
        if isinstance(ts, SnapshotHandle):
            ts = ts.ts
        if ts is None:
            ts = self.current_ts()
        records = jnp.asarray(records, jnp.int32)
        ts_vec = jnp.full((records.shape[0],), int(ts), jnp.int32)
        return resolve_sharded(self.store.versions, records, ts_vec,
                               mesh=self.mesh, axis=self.cc_axis)

    def run_readonly_batch(self, batch: TxnBatch,
                           ts: Optional[int] = None
                           ) -> Tuple[jax.Array, jax.Array,
                                      Dict[str, jax.Array]]:
        """Execute a batch of read-only transactions against the snapshot
        at ``ts``: no CC phase, no placeholder versions, no writes to any
        shared state — reads resolve purely through the sharded version
        rings in ONE jitted step (this is the hot scan path;
        ``snapshot_read`` is the flexible per-call variant).
        Returns (read_vals [T, Rd, D], found [T, Rd], metrics)."""
        if isinstance(ts, SnapshotHandle):
            ts = ts.ts
        if ts is None:
            ts = self.current_ts()
        with self.tracer.span("engine/readonly", txns=batch.size,
                              ts=int(ts)):
            vals, found, metrics = self._readonly(
                self.store.versions, batch.read_set,
                jnp.asarray(int(ts), jnp.int32))
        return vals, found, metrics

    # -- K-ring pressure diagnostics ---------------------------------------
    def record_commit_metrics(self, metrics: Dict[str, jax.Array],
                              n_txns: int = 0) -> None:
        """Fold a commit's metric outputs into the registry (called by
        run_batch and by TxnService for pipelined commits). Every
        accumulation is a lazy device-side add — an ``int()`` here would
        join the host on every commit and serialize the scheduler's
        dispatch-ahead pipeline; ``registry.snapshot()`` (or the legacy
        stats surfaces) convert on demand. Live and dead evictions
        accumulate separately: only the live histogram feeds the
        spill/adaptive-K policy."""
        m = self.metrics
        for key in ("ring_overwrote_rec", "ring_overwrote_dead_rec",
                    "ring_overwrote_live", "ring_overwrote_dead",
                    "paged_alloc_failed", "aborts", "waves",
                    *self._SPILL_KEYS, *self._SHARD_KEYS):
            if key in metrics:
                m.accumulate(f"engine/{key}", metrics[key])
        m.inc("engine/commits")
        m.inc("engine/txns_committed", n_txns)
        self._commits_since_sweep += 1
        # lifecycle audit: fold state counters, stash the lazy audit_*
        # arrays (popped from ``metrics`` so result fan-out stays clean)
        self.auditor.on_commit(metrics)

    def overflow_by_record(self) -> jax.Array:
        """[R] cumulative count of LIVE version evictions per record —
        how often each key's reader-visible snapshot history was pushed
        out of the primary K-ring (and offered to the spill tier) since
        the last reset. Dead evictions (no registered pin inside the
        version's window, end below the future-reader floor) are tracked
        separately — see ``overflow_stats``."""
        return to_global(self.store.versions,
                         self.metrics.peek("engine/ring_overwrote_rec"))

    def overflow_stats(self, top_k: int = 8) -> Dict[str, object]:
        """Host-side K-ring pressure summary: total LIVE evictions, the
        top-k hottest records, and a histogram of per-record live-eviction
        counts (powers-of-two buckets) — the adaptive-K policy input.
        Dead evictions (versions no legal reader could still resolve)
        are split out under ``dead_*`` keys and never enter the live
        histogram. Diagnostic API — synchronises."""
        counts = self.overflow_by_record()
        dead = to_global(self.store.versions,
                         self.metrics.peek("engine/ring_overwrote_dead_rec"))
        k = min(top_k, self.num_records)
        top_vals, top_recs = jax.lax.top_k(counts, k)
        edges = [0, 1, 2, 4, 8, 16, 32, 64]
        hist = _bucket_histogram(counts, edges)
        return {
            "total_overwrites": int(jnp.sum(counts)),
            "records_affected": int(jnp.sum(counts > 0)),
            "top_records": [(int(r), int(v))
                            for r, v in zip(top_recs, top_vals) if v > 0],
            "histogram": hist,
            "dead_overwrites": int(jnp.sum(dead)),
            "dead_histogram": _bucket_histogram(dead, edges),
        }

    def spill_stats(self) -> Dict[str, int]:
        """Spill-tier summary: current pool occupancy/capacity plus the
        cumulative admitted / dropped / pinned-overwrite counters (the
        found=False budget historical reads are exposed to)."""
        spill = self.store.versions.spill
        occupancy = 0 if spill is None else int(jnp.sum(spill.rec >= 0))
        capacity = 0 if spill is None else (
            self.n_shards * self.spill_buckets * self.spill_slots)
        return dict({k: int(self.metrics.value(f"engine/{k}"))
                     for k in self._SPILL_KEYS},
                    spill_occupancy=occupancy, spill_capacity=capacity)

    def storage_stats(self) -> Dict[str, object]:
        """Physical storage summary (the paged-store headline number):
        how many version slots the primary level allocates and how full
        they are, against the dense-equivalent footprint ``R x k_max``.
        ``physical_slots`` counts ALLOCATED slot capacity on one
        consistent base (dense: all of R x k_max; paged: the whole
        slab, free-list pages included — ``mapped_slots`` is the
        in-use subset); ``physical_version_words`` prices the same base
        at the per-slot (begin, end, payload) word cost plus the paged
        page tables, so layouts are comparable in words of memory.
        Diagnostic API — synchronises."""
        D = self.workload.payload_words
        versions = self.store.versions
        dense_slots = self.num_records * self.k_max
        stats: Dict[str, int] = {
            "layout": "paged" if self.paged else "dense",
            "num_records": self.num_records,
            "k_max": self.k_max,
            "dense_equiv_slots": dense_slots,
            "dense_equiv_words": dense_slots * (2 + D),
            "slot_occupancy": int(jnp.sum(store_occupancy(versions))),
        }
        if self.paged:
            pages = versions.pages
            mapped = int(jnp.sum(pages.page_table >= 0))
            total = self.n_shards * self.pages_per_shard
            stats.update({
                "page_slots": self.page_slots,
                "pages_total": total,
                "pages_mapped": mapped,
                "pages_free": total - mapped,
                # one consistent base: the whole slab is allocated
                # memory (free-list pages included); mapped_slots is
                # the in-use subset
                "physical_slots": total * self.page_slots,
                "mapped_slots": mapped * self.page_slots,
                # slab + page tables; tables cost one i32 per entry
                "physical_version_words": (
                    total * self.page_slots * (2 + D)
                    + self.n_shards * versions.records_per_shard
                    * pages.max_pages),
                "alloc_failed": int(
                    self.metrics.value("engine/paged_alloc_failed")),
            })
        else:
            stats.update({
                "physical_slots": dense_slots,
                "physical_version_words": dense_slots * (2 + D),
            })
        return stats

    def health(self) -> Dict[str, object]:
        """MVCC health gauges (watermark lag, pin ages, ring/slab/spill
        saturation, pressure percentiles) — derived from store state on
        demand, one transfer. See ``repro.obs.health``. Diagnostic API —
        synchronises."""
        return engine_health(self)

    def inspect_record(self, record: int):
        """Time-travel inspector for one record (requires an enabled
        ``auditor``): resident versions across ring/slab/spill merged
        with the harvested transition events — see
        ``repro.obs.LifecycleAuditor.inspect_record``."""
        if not self.auditor.enabled:
            raise RuntimeError(
                "inspect_record requires BohmEngine(auditor=...)")
        return self.auditor.inspect_record(record)


def _bucket_histogram(counts: jax.Array, edges: List[int]
                      ) -> List[Tuple[str, int]]:
    """[(bucket label, n_records)] for counts bucketed by [lo, hi)."""
    out = []
    for i, lo in enumerate(edges):
        hi = edges[i + 1] if i + 1 < len(edges) else None
        if hi is None:
            n = int(jnp.sum(counts >= lo))
            label = f"{lo}+"
        else:
            n = int(jnp.sum((counts >= lo) & (counts < hi)))
            label = f"{lo}" if hi == lo + 1 else f"{lo}-{hi - 1}"
        out.append((label, n))
    return out


# ---------------------------------------------------------------------------
# The phase graph. Each phase is a separate jit so a scheduler can compose
# them across batches:
#   * plan_phase has NO data dependency on any store — CC(b+1) dispatches
#     while exec(b) is in flight (it needs only the batch content and the
#     host-mirrored timestamp base);
#   * exec_phase depends only on the committed ``store.base`` rows in the
#     batch's read-set — exec(b+1) dispatches BEFORE commit(b) when the two
#     batches' record footprints are disjoint (deferred commit);
#   * commit_phase is the batch barrier: the data dependency on the
#     previous commit's store IS the paper's one synchronisation point.
# ---------------------------------------------------------------------------
def plan_phase(batch: TxnBatch, ts_base: jax.Array, *, mesh,
               cc_axis: str) -> Plan:
    """CC phase: timestamps + placeholder versions + read annotations,
    record-partitioned over the mesh when one is present."""
    if mesh is not None and cc_axis in mesh.shape and \
            mesh.shape[cc_axis] > 1:
        sharded = plan_mod.cc_plan_sharded(batch, ts_base, mesh, cc_axis)
        return plan_mod.merge_sharded_plan(sharded, batch)
    return cc_plan(batch, ts_base)


def exec_phase(plan: Plan, batch: TxnBatch, store: Store, *,
               workload: Workload
               ) -> Tuple[jax.Array, jax.Array, Dict[str, jax.Array]]:
    """Execution wavefront only — produces the batch's version payloads
    without touching the store. Returns (w_data, read_vals, metrics)."""
    return execute_plan(plan, batch, store, workload)


def commit_phase(plan: Plan, batch: TxnBatch, store: Store,
                 w_data: jax.Array,
                 watermark: Optional[jax.Array] = None,
                 ts_window: Optional[Tuple[jax.Array, jax.Array]] = None,
                 pin_ts: Optional[jax.Array] = None,
                 *, mesh, cc_axis: str, with_audit: bool = False
                 ) -> Tuple[Store, Dict[str, jax.Array]]:
    """Watermark-driven sharded commit of an executed epoch. ``ts_window``
    (default: the plan's own [ts_base, ts_base + T) span) makes the
    global-timestamp accounting explicit so merged epochs and deferred
    commits land ``ts_counter`` exactly where the sequential schedule
    would. ``pin_ts`` (the registered snapshot pins at plan time) drives
    the pin-precise live/dead eviction split and spill admission."""
    return commit(plan, batch, store, w_data, watermark,
                  mesh=mesh, cc_axis=cc_axis, ts_window=ts_window,
                  pin_ts=pin_ts, with_audit=with_audit)


def exec_commit_phase(plan: Plan, batch: TxnBatch, store: Store,
                      watermark: Optional[jax.Array] = None,
                      pin_ts: Optional[jax.Array] = None, *,
                      workload: Workload, mesh, cc_axis: str):
    """Fused exec + commit (the pre-phase-split shape, kept as the
    composition it always was — ``_bohm_step`` builds on it)."""
    w_data, read_vals, metrics = exec_phase(plan, batch, store,
                                            workload=workload)
    new_store, ring_metrics = commit_phase(plan, batch, store, w_data,
                                           watermark, pin_ts=pin_ts,
                                           mesh=mesh, cc_axis=cc_axis)
    metrics = dict(metrics, **ring_metrics)
    return new_store, read_vals, metrics


def _bohm_step(store: Store, batch: TxnBatch,
               watermark: Optional[jax.Array] = None,
               pin_ts: Optional[jax.Array] = None, *,
               workload: Workload, mesh, cc_axis: str):
    # --- CC phase: timestamps + placeholder versions + read annotations ---
    plan = plan_phase(batch, store.ts_counter, mesh=mesh, cc_axis=cc_axis)
    # --- batch barrier (the only synchronisation point) -------------------
    # --- execution phase + watermark-driven GC / commit -------------------
    return exec_commit_phase(plan, batch, store, watermark, pin_ts,
                             workload=workload, mesh=mesh, cc_axis=cc_axis)


def _readonly_resolve(versions, read_set: jax.Array, ts: jax.Array, *,
                      mesh, cc_axis: str):
    """One fused device step for a read-only batch: per-shard gather of
    candidate windows, visibility through the Pallas kernel, pad mask."""
    T, Rd = read_set.shape
    flat = jnp.maximum(read_set.reshape(-1), 0)
    ts_vec = jnp.full((flat.shape[0],), ts, jnp.int32)
    vals, found = resolve_sharded(versions, flat, ts_vec, mesh=mesh,
                                  axis=cc_axis)
    valid = read_set >= 0
    vals = jnp.where(valid[..., None], vals.reshape(T, Rd, -1), 0)
    found = jnp.where(valid, found.reshape(T, Rd), True)
    occ = store_occupancy(versions)
    n_valid = jnp.maximum(jnp.sum(valid), 1)
    metrics = {"found_frac": jnp.sum(found & valid) / n_valid,
               "ring_occ_max": jnp.max(occ)}
    return vals, found, metrics


# ---------------------------------------------------------------------------
# Serial oracle (serializability ground truth): execute transactions one by
# one in timestamp order against a single-version store.
# ---------------------------------------------------------------------------
def serial_oracle(store_base: jax.Array, batch: TxnBatch,
                  workload: Workload) -> Tuple[jax.Array, jax.Array]:
    """Returns (final_base [R, D], read_vals [T, Rd, D])."""
    R = store_base.shape[0]

    def step(base, txn):
        read_set, write_set, txn_type, args = txn
        vals = base[jnp.maximum(read_set, 0)]                 # [Rd, D]
        vals = jnp.where((read_set >= 0)[..., None], vals, 0)
        write_vals, _ = jax.lax.switch(txn_type, list(workload.branches),
                                       vals, args)
        # unused write slots index one past the end and are dropped
        rec = jnp.where(write_set >= 0, write_set, R)
        return base.at[rec].set(write_vals, mode="drop"), vals

    final, reads = jax.lax.scan(
        step, store_base,
        (batch.read_set, batch.write_set, batch.txn_type, batch.args))
    return final, reads


def serial_oracle_prefix(store_base: jax.Array, batch: TxnBatch,
                         workload: Workload, n_txns: int) -> jax.Array:
    """Oracle state after only the first ``n_txns`` of ``batch`` — the
    ground truth for a snapshot read at ts = ts_base + n_txns."""
    prefix = jax.tree.map(lambda x: x[:n_txns], batch)
    final, _ = serial_oracle(store_base, prefix, workload)
    return final
