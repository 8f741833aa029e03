"""Bohm execution phase (paper §4.2), deterministic wavefront formulation.

The paper's execution threads claim transactions with a CAS and recursively
evaluate unproduced read dependencies. The TPU-native equivalent is a
wavefront: each iteration of a ``lax.while_loop`` executes *every*
transaction whose read dependencies are all Complete (the paper's state
machine collapses to a boolean ``done`` vector; "Executing" has no meaning
when a wave is a single fused vector step). The number of waves equals the
longest read-dependency chain in the batch — writes NEVER add waves
(write-write ordering was fully resolved by the CC phase; paper §4.2.1:
"T2 could execute before T1 despite their write-sets overlapping").

Reads perform no writes to shared state: each wave gathers read values from
the version buffer / base store, computes transaction logic, and scatters
produced values into the transaction's OWN placeholder slots.
"""
from __future__ import annotations

import dataclasses
from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding
from jax.sharding import PartitionSpec as P

from repro.core.plan import Plan
from repro.core.txn import TxnBatch, Workload
from repro.store import (ShardedVersionStore, commit_sharded,
                         init_sharded_store)


@jax.tree_util.register_dataclass
@dataclasses.dataclass(frozen=True)
class Store:
    """Committed state: single-version heads + the persistent version store.

    ``base`` caches each record's head (open) version — the common-case
    read target of the execution wavefront, kept dense so in-batch reads
    stay a single [R, D] gather. ``versions`` is the multiversion source of
    truth: per-record rings of (begin_ts, end_ts, payload) that persist
    across batch barriers so snapshot readers at older timestamps can
    resolve visibility long after the head has moved on, record-partitioned
    over the ``cc`` mesh axis (``repro.store.sharded``; n_shards == 1 is
    the plain single ring). Reclamation is watermark-driven (GC conditions
    1+2, see repro/store/ring.py), not tied to the barrier.
    """
    base: jax.Array       # [R, D] head-version payloads
    base_ts: jax.Array    # [R] begin ts of the head version
    ts_counter: jax.Array        # [] next timestamp to assign
    versions: ShardedVersionStore  # [n, Rl, K] cross-batch version rings


def init_store(num_records: int, payload_words: int,
               init_value: int = 0, ring_slots: int = 4,
               n_shards: int = 1, spill_buckets: int = 0,
               spill_slots: int = 0,
               k_init: Optional[int] = None,
               paged: bool = False, page_slots: int = 4,
               pages_per_shard: Optional[int] = None,
               mesh=None, axis: str = "cc") -> Store:
    """Store whose every record holds ``init_value`` (head + ring slot
    0), laid out on ``mesh`` as ``store_from_base`` says."""
    def build():
        base = jnp.full((num_records, payload_words), init_value, jnp.int32)
        base_ts = jnp.zeros((num_records,), jnp.int32)
        return _store(base, base_ts, ring_slots, n_shards,
                      spill_buckets=spill_buckets, spill_slots=spill_slots,
                      k_init=k_init, paged=paged, page_slots=page_slots,
                      pages_per_shard=pages_per_shard)
    return _build_on(mesh, axis, n_shards, build)


def store_from_base(base: jax.Array, base_ts: Optional[jax.Array] = None,
                    ring_slots: int = 4, n_shards: int = 1,
                    spill_buckets: int = 0, spill_slots: int = 0,
                    k_init: Optional[int] = None,
                    paged: bool = False, page_slots: int = 4,
                    pages_per_shard: Optional[int] = None,
                    mesh=None, axis: str = "cc") -> Store:
    """Store whose initial state (head + ring slot 0) is ``base``. The head
    is a copy of ``base`` (and of ``base_ts``): the commit updates the
    store's arrays in place, which must not reach the caller's.

    With a ``mesh`` whose ``axis`` has ``n_shards`` devices, the store is
    built on it: each device computes only its own shards' rings, page
    slabs, spill pools and ``k_eff`` (every [n, R/n, ...] version leaf
    split over ``axis``), and the head, ``base_ts`` and the ts counter
    are replicated. No device ever holds the whole version store. Any
    other mesh (or none) builds the store on the default device."""
    def build(base, base_ts):
        base = jnp.array(base, jnp.int32, copy=True)
        base_ts = (jnp.zeros((base.shape[0],), jnp.int32) if base_ts is None
                   else jnp.array(base_ts, jnp.int32, copy=True))
        return _store(base, base_ts, ring_slots, n_shards,
                      spill_buckets=spill_buckets, spill_slots=spill_slots,
                      k_init=k_init, paged=paged, page_slots=page_slots,
                      pages_per_shard=pages_per_shard)
    return _build_on(mesh, axis, n_shards, build, base, base_ts)


def _store(base, base_ts, ring_slots, n_shards, **versions) -> Store:
    return Store(base=base, base_ts=base_ts,
                 ts_counter=jnp.ones((), jnp.int32),
                 versions=init_sharded_store(base, base_ts, ring_slots,
                                             n_shards, **versions))


def _build_on(mesh, axis: str, n_shards: int, build, *args) -> Store:
    """``build(*args)`` eagerly on the default device, or, where ``mesh``
    splits ``axis`` into ``n_shards`` devices, as one program whose
    outputs are made where they live: the version leaves split over
    ``axis``, the rest replicated (the arguments are replicated first)."""
    if mesh is None or mesh.shape.get(axis) != n_shards:
        return build(*args)
    split = NamedSharding(mesh, P(axis))
    whole = NamedSharding(mesh, P())
    shape = jax.eval_shape(build, *args)
    out = Store(base=whole, base_ts=whole, ts_counter=whole,
                versions=jax.tree.map(lambda _: split, shape.versions))
    return jax.jit(build, out_shardings=out)(*jax.device_put(args, whole))


def execute_plan(plan: Plan, batch: TxnBatch, store: Store,
                 workload: Workload
                 ) -> Tuple[jax.Array, jax.Array, Dict[str, jax.Array]]:
    """Run the wavefront. Returns (w_data [Nw, D], read_vals [T, Rd, D],
    metrics)."""
    T, Rd = batch.read_set.shape
    Nw = plan.w_rec.shape[0]
    D = store.base.shape[1]

    base_reads = store.base[jnp.maximum(batch.read_set, 0)]   # [T, Rd, D]

    def cond(state):
        done, _, _, _, waves = state
        return ~jnp.all(done)

    def body(state):
        done, w_data, read_out, aborted, waves = state
        dep_done = jnp.where(plan.r_dep_txn >= 0,
                             done[jnp.maximum(plan.r_dep_txn, 0)], True)
        ready = ~done & jnp.all(dep_done, axis=1)

        # gather read values: in-batch version slot or base head
        slot = jnp.maximum(plan.r_dep_slot, 0)
        vals = jnp.where((plan.r_dep_slot >= 0)[..., None],
                         w_data[slot], base_reads)             # [T, Rd, D]
        vals = jnp.where((batch.read_set >= 0)[..., None], vals, 0)

        write_vals, abort = workload.apply(batch.txn_type, vals, batch.args)
        # abort => copy-forward predecessor values into own versions
        # (branches already return read values for aborted paths; the flag
        # is surfaced in metrics only).

        # scatter produced values into this txn's placeholder slots
        w_slot = plan.w_slot                                   # [T, W]
        take = ready[:, None] & (w_slot >= 0)
        flat_slot = jnp.where(take, w_slot, Nw).reshape(-1)
        flat_vals = write_vals.reshape(-1, D)
        w_data = jnp.concatenate([w_data, jnp.zeros((1, D), w_data.dtype)])
        w_data = w_data.at[flat_slot].set(
            jnp.where(take.reshape(-1, 1), flat_vals, 0),
            mode="drop")[:-1]

        read_out = jnp.where(ready[:, None, None], vals, read_out)
        # abort flags fold into the loop state at each txn's ready wave
        # (its read values are final there) — no post-loop re-apply
        aborted = jnp.where(ready, abort, aborted)
        return (done | ready, w_data, read_out, aborted, waves + 1)

    done0 = jnp.zeros((T,), bool)
    w_data0 = jnp.zeros((Nw, D), jnp.int32)
    read0 = jnp.zeros((T, Rd, D), jnp.int32)
    done, w_data, read_out, aborted, waves = jax.lax.while_loop(
        cond, body, (done0, w_data0, read0, jnp.zeros((T,), bool),
                     jnp.zeros((), jnp.int32)))

    metrics = {"waves": waves, "aborts": jnp.sum(aborted)}
    return w_data, read_out, metrics


def commit(plan: Plan, batch: TxnBatch, store: Store, w_data: jax.Array,
           watermark: Optional[jax.Array] = None, mesh=None,
           cc_axis: str = "cc",
           ts_window: Optional[Tuple[jax.Array, jax.Array]] = None,
           pin_ts: Optional[jax.Array] = None,
           with_audit: bool = False
           ) -> Tuple[Store, Dict[str, jax.Array]]:
    """Batch barrier: fold each record's batch-final version into the head
    cache AND commit every batch version into the persistent (sharded)
    rings, where eviction is governed by the low watermark (min active
    reader snapshot ts). With no active readers the watermark defaults to
    the pre-batch timestamp counter, so superseded versions die one
    barrier after they are closed — the seed's Condition-3 behaviour falls
    out as the degenerate no-reader case.

    ``ts_window`` = (ts_lo, ts_hi) is the half-open global-timestamp span
    this commit covers. It defaults to the single-batch window
    ``[plan.ts_base, plan.ts_base + T)`` but is EXPLICIT so merged CC
    epochs (several admitted batches, one commit) and deferred commits
    (exec of a footprint-disjoint successor dispatched first) land the
    counter exactly where the sequential schedule would, and so the ring
    layer can hold the GC watermark at <= ts_lo — the condition that keeps
    the paper's reclamation rules (§4.2.2, conditions 1+2) unchanged no
    matter where in the pipeline the commit runs.

    ``pin_ts`` [P] — the registered snapshot pins (INF_TS-padded), the
    input to the ring layer's pin-precise live/dead eviction split and
    the spill tier's admission/victim decisions.
    """
    if watermark is None:
        watermark = store.ts_counter
    if ts_window is None:
        ts_window = (plan.ts_base,
                     plan.ts_base + batch.read_set.shape[0])
    R = store.base.shape[0]
    # stage scopes (``commit/head`` here, ``commit/ring`` and
    # ``commit/spill`` in the store) name the ops in the program's
    # metadata only; the device trace reads each stage's time from them
    with jax.named_scope("commit/head"):
        # one batch-final version per record; pads index past the end and
        # drop, so both scatters write the (donated) head in place
        rec = jnp.where(plan.commit_mask, plan.w_rec, R)
        base = store.base.at[rec].set(w_data, mode="drop")
        ts = plan.ts_base + plan.w_txn
        base_ts = store.base_ts.at[rec].set(
            jnp.where(plan.commit_mask, ts, 0), mode="drop")
    versions, ring_metrics = commit_sharded(
        store.versions, plan.w_rec, plan.w_key, plan.w_valid,
        plan.w_begin_ts, plan.w_end_ts, w_data, watermark,
        mesh=mesh, axis=cc_axis, ts_window=ts_window, pin_ts=pin_ts,
        with_audit=with_audit)
    return Store(base=base, base_ts=base_ts,
                 ts_counter=jnp.asarray(ts_window[1], jnp.int32),
                 versions=versions), ring_metrics
