"""Single-shard persistent version ring: per-record slots + precise GC.

This is the storage kernel of the multiversion store: a fixed-K per-record
version ring that PERSISTS across batch barriers,

    begin   [R, K] i32   version begin timestamp (INF_TS = empty slot)
    end     [R, K] i32   version end timestamp   (INF_TS = still open)
    payload [R, K, D]    version payloads
    head    [R]    i32   next ring position (insert cursor, mod K)

with reclamation driven by a **low watermark** = min(active reader
snapshot ts, next unassigned ts). GC conditions 1+2 (paper §4.2.2): a
version may be reclaimed exactly when its end timestamp is <= the
watermark — some transaction wrote a newer version (end is closed) AND no
active or future reader can have a snapshot timestamp inside [begin, end).
Versions above the watermark survive the barrier, which is what lets
read-only transactions run against older snapshots while update batches
stream through (the paper's Fig 9/10 scenario).

Slots are NOT kept sorted — the ``mvcc_resolve`` Pallas kernel resolves
visibility by a K-wide interval test + max-begin reduction, which is
order-independent, so insertion is pure ring arithmetic: the j-th new
version of record r in a batch lands in slot (head[r] + j) % K.

Overflow policy (K-bounded): when a record accumulates more than K live
versions, the ring keeps the NEWEST K and the oldest are evicted even if
they sit above the watermark. Eviction liveness is PIN-PRECISE: an
evicted version is *live* exactly when a registered snapshot pin lands
inside its [begin, end) window or its end timestamp still reaches future
readers (``pin_stabbed``); everything else superseded between the lowest
pin and "now" is dead — no legal reader can ever resolve to it.  Live
evictions are offered to the secondary spill store (``repro.store.spill``
— pass ``with_evictees=True`` to collect them); dead ones are discarded
and counted separately (``ring_overwrote_dead``), so the spill/adaptive-K
policy reacts only to real history loss.  Without a spill tier a live
eviction still never yields a stale read: every version older than the
evicted one has end <= the evicted version's begin <= the reader's ts, so
the interval test rejects it and the read reports found=False.

Per-record ring capacity is ``k_eff`` (<= K, the physical slot count):
the adaptive-K policy (``repro.store.policy``) grows hot records' rings
and shrinks cold ones within a fixed slot budget; insertion is confined
to slots [0, k_eff) while resolution and GC scan all K slots, so a shrink
leaves stranded versions readable until the watermark passes them.

Record-partitioned (sharded) rings build on this module — see
``repro.store.sharded.ShardedVersionStore``, which runs this commit path
per shard over the ``cc`` mesh axis.
"""
from __future__ import annotations

import dataclasses
from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.kernels import ops

INF_TS = jnp.iinfo(jnp.int32).max

# Version-lifecycle audit state codes. They are defined HERE (not in
# ``repro.obs.lifecycle``, which re-exports them) because the store's
# commit paths stamp them device-side when ``with_audit=True`` and the
# store must not import the obs layer. Code 0 = masked / no event.
AUDIT_COMMITTED = 1        # version inserted into the primary store
AUDIT_OVERWROTE_LIVE = 2   # pin-live version destroyed by a K-overflow
AUDIT_OVERWROTE_DEAD = 3   # dead (unreachable) version destroyed
AUDIT_SPILLED = 4          # live evictee placed into the spill pool
AUDIT_SPILL_DROPPED = 5    # live evictee offered to spill, bucket full
AUDIT_SPILL_OVERWROTE = 6  # spill-resident version lost to a newer one
AUDIT_PAGE_DROPPED = 7     # insert lost: page-table allocation failed
AUDIT_GC_RECLAIMED = 8     # reclaimed by a watermark sweep (audited GC)

AUDIT_STATE_NAMES = {
    AUDIT_COMMITTED: "committed",
    AUDIT_OVERWROTE_LIVE: "overwritten_live",
    AUDIT_OVERWROTE_DEAD: "overwritten_dead",
    AUDIT_SPILLED: "spilled",
    AUDIT_SPILL_DROPPED: "spill_dropped",
    AUDIT_SPILL_OVERWROTE: "spill_overwritten",
    AUDIT_PAGE_DROPPED: "page_dropped",
    AUDIT_GC_RECLAIMED: "gc_reclaimed",
}


def pin_stabbed(begin: jax.Array, end: jax.Array,
                pin_ts: Optional[jax.Array]) -> jax.Array:
    """Elementwise: does any registered snapshot pin land inside
    [begin, end)?  ``pin_ts`` is a [P] i32 array padded with INF_TS (a pad
    pin never stabs: INF_TS < end is false for every closed version).
    With ``pin_ts=None`` nothing is stabbed."""
    if pin_ts is None:
        return jnp.zeros(jnp.shape(begin), bool)
    p = pin_ts.reshape((1,) * jnp.ndim(begin) + (-1,))
    return jnp.any((begin[..., None] <= p) & (p < end[..., None]), axis=-1)


@jax.tree_util.register_dataclass
@dataclasses.dataclass(frozen=True)
class VersionRing:
    begin: jax.Array     # [R, K] i32
    end: jax.Array       # [R, K] i32
    payload: jax.Array   # [R, K, D]
    head: jax.Array      # [R] i32

    @property
    def num_slots(self) -> int:
        return self.begin.shape[-1]

    @property
    def num_records(self) -> int:
        return self.begin.shape[-2]


def init_ring(base: jax.Array, base_ts: jax.Array,
              num_slots: int = 4) -> VersionRing:
    """Ring whose slot 0 holds the initial open version of every record."""
    R, D = base.shape
    begin = jnp.full((R, num_slots), INF_TS, jnp.int32)
    begin = begin.at[:, 0].set(jnp.asarray(base_ts, jnp.int32))
    end = jnp.full((R, num_slots), INF_TS, jnp.int32)
    payload = jnp.zeros((R, num_slots, D), base.dtype)
    payload = payload.at[:, 0, :].set(base)
    head = jnp.full((R,), 1 % num_slots, jnp.int32)
    return VersionRing(begin=begin, end=end, payload=payload, head=head)


def ring_occupancy(ring: VersionRing) -> jax.Array:
    """[R] live (non-garbage) version count per record."""
    return jnp.sum(ring.begin != INF_TS, axis=-1).astype(jnp.int32)


def ring_fill_fraction(occupancy: jax.Array,
                       k_eff: jax.Array) -> jax.Array:
    """Per-record ring pressure in [0, 1]: live versions over effective
    capacity. 1.0 means the next superseding write evicts history —
    the distribution's upper percentiles are the obs layer's early
    warning for found=False exposure (works elementwise on [R] or
    stacked [n, Rl] inputs)."""
    return occupancy / jnp.maximum(k_eff, 1).astype(jnp.float32)


def gather_windows(ring: VersionRing, records: jax.Array
                   ) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """Pre-gather per-read candidate windows for ``mvcc_resolve``:
    records [B] -> (begin [B, K], end [B, K], payload [B, K, D])."""
    rec = jnp.maximum(jnp.asarray(records, jnp.int32), 0)
    return ring.begin[rec], ring.end[rec], ring.payload[rec]


def commit_versions(ring: VersionRing, w_rec: jax.Array, w_key: jax.Array,
                    w_valid: jax.Array, w_begin_ts: jax.Array,
                    w_end_ts: jax.Array, w_data: jax.Array,
                    watermark: jax.Array,
                    ts_window: Optional[Tuple[jax.Array, jax.Array]] = None,
                    k_eff: Optional[jax.Array] = None,
                    pin_ts: Optional[jax.Array] = None,
                    with_evictees: bool = False,
                    with_audit: bool = False
                    ) -> Tuple[VersionRing, Dict[str, jax.Array]]:
    """Batch-barrier ring maintenance: GC conditions 1+2, then commit ALL
    of the batch's versions (not just segment-final ones).

      1. reclaim every version with end <= watermark (no active or future
         reader can see it) — precise GC, versions above the mark survive;
      2. close the previously-open head version of each written record
         (its end becomes the record's first in-batch begin timestamp);
      3. insert the batch's versions at (head + rank) % K, keeping the
         newest K per record when a segment overflows the ring.

    Inputs are the plan's sorted placeholder arrays ([Nw], pads invalid)
    plus the produced payloads ``w_data`` [Nw, D]. ``w_key`` need only be
    sorted *within* contiguous shard blocks (as ``merge_sharded_plan``
    emits) — a stable re-sort here restores the global record order.

    ``ts_window`` = (ts_lo, ts_hi), the global-timestamp span this commit
    covers, clamps the eviction watermark to ``min(watermark, ts_lo)``: a
    legal watermark never exceeds the epoch's first timestamp (it is
    min(active reader snapshots, ts at plan time)), so a well-scheduled
    caller sees NO behaviour change — the clamp pins GC conditions 1+2
    in place when merged epochs or deferred commits hand the window in
    out of lock-step with the ring's own notion of "now".

    ``k_eff`` [R] bounds each record's insertions to its first k_eff[r]
    slots (adaptive per-record capacity; default: all K physical slots).
    ``pin_ts`` [P] (registered snapshot pins, INF_TS-padded) drives the
    pin-precise live/dead split of evicted versions; without it liveness
    degrades to the watermark test ``end > watermark`` (the historical
    over-approximation).  ``with_evictees=True`` additionally returns the
    evicted versions' (rec, begin, end, payload, live) arrays in the
    metrics dict under ``evict_*`` keys — the spill store's input.

    Record ids must already be LOCAL to this ring (callers with a sharded
    store mask foreign records to INF_TS / valid=False and divide owned
    ids down to the shard-local index before calling).
    """
    R, K = ring.begin.shape
    watermark = jnp.asarray(watermark, jnp.int32)
    if ts_window is not None:
        watermark = jnp.minimum(watermark,
                                jnp.asarray(ts_window[0], jnp.int32))
    k_arr = (jnp.full((R,), K, jnp.int32) if k_eff is None
             else jnp.asarray(k_eff, jnp.int32))
    # future readers pin at >= ts_hi - 1 (the epoch's last assigned ts):
    # an evicted version with end above the floor is still reachable.
    # Without a window the floor degrades to the watermark — the legacy
    # ``end > watermark`` liveness for bare-ring callers.
    floor = (jnp.asarray(ts_window[1], jnp.int32) - 1
             if ts_window is not None else watermark)

    # -- 1. precise reclamation below the watermark ------------------------
    live = ring.begin != INF_TS
    dead = live & (ring.end <= watermark)          # open versions: end==INF
    evicted = jnp.sum(dead)
    begin = jnp.where(dead, INF_TS, ring.begin)
    end = jnp.where(dead, INF_TS, ring.end)

    # -- 2. close the open head version of every written record ------------
    first_ts = jnp.full((R,), INF_TS, jnp.int32).at[
        jnp.where(w_valid, w_rec, R)].min(
        jnp.where(w_valid, w_begin_ts, INF_TS), mode="drop")
    open_slot = (end == INF_TS) & (begin != INF_TS)
    end = jnp.where(open_slot & (first_ts != INF_TS)[:, None],
                    first_ts[:, None], end)

    # -- 3. insert the batch's versions (newest k_eff[r] per record) -------
    order = jnp.argsort(w_key, stable=True)        # record-major, pads last
    rec_s = w_rec[order]
    valid_s = w_valid[order]
    beg_s = w_begin_ts[order]
    end_s = w_end_ts[order]
    data_s = w_data[order]

    left = jnp.searchsorted(rec_s, rec_s, side="left")
    right = jnp.searchsorted(rec_s, rec_s, side="right")
    count = (right - left).astype(jnp.int32)
    rank = jnp.arange(rec_s.shape[0], dtype=jnp.int32) - left.astype(
        jnp.int32)
    safe_rec = jnp.clip(rec_s, 0, R - 1)
    k_rec = k_arr[safe_rec]                        # per-record capacity
    drop_n = jnp.maximum(count - k_rec, 0)         # overflow: drop oldest
    keep = valid_s & (rank >= drop_n)
    slot = (ring.head[safe_rec] + rank - drop_n) % k_rec
    # the ring is donated to the commit and written in place: begin and
    # end are read and scattered by (record, slot) pairs in their stored
    # [R, K] shape (a flattened [R * K] view is a relayout on a tiled
    # device), and the payload rows go through the in-place row kernel,
    # which also returns the rows it overwrites. Dropped entries read the
    # last slot and write to record R, out of range.
    put_rec = jnp.where(keep, safe_rec, R)
    get_rec = jnp.where(keep, safe_rec, R - 1)
    get_slot = jnp.where(keep, slot, K - 1)
    tgt_begin = begin[get_rec, get_slot]
    tgt_end = end[get_rec, get_slot]
    payload, tgt_payload = ops.update_rows(ring.payload, safe_rec, slot,
                                           keep, data_s,
                                           with_old=with_evictees)
    # liveness of what this insert destroys: pin-precise — a registered
    # snapshot pin inside [begin, end), or end reaching the future-reader
    # floor. Versions superseded between the lowest pin and "now" stab no
    # pin and sit below the floor: DEAD, however far above the watermark
    # their end is (the old ``end > watermark`` test miscounted those).
    hit_any = keep & (tgt_begin != INF_TS)
    tgt_live = (tgt_end > floor) | pin_stabbed(tgt_begin, tgt_end, pin_ts)
    hit_live = hit_any & tgt_live
    hit_dead = hit_any & ~tgt_live
    # per-record live-overwrite counts: the K-ring pressure histogram the
    # spill/adaptive-K policy consumes; dead overwrites are bookkeeping
    # noise and are split out so the policy never reacts to them
    overwrote_rec = jnp.zeros((R,), jnp.int32).at[
        jnp.where(hit_live, safe_rec, R)].add(1, mode="drop")
    overwrote_dead_rec = jnp.zeros((R,), jnp.int32).at[
        jnp.where(hit_dead, safe_rec, R)].add(1, mode="drop")

    # within-batch overflow drops (never inserted) face the same test
    dropped = valid_s & ~keep
    drop_live = dropped & ((end_s > floor) | pin_stabbed(beg_s, end_s,
                                                         pin_ts))

    if with_evictees:
        # old contents of the slots this insert destroys, read before they
        # are written (targets are distinct, so pre-write state is the
        # pre-batch state) + the live within-batch drops: the spill input.
        ev_rec = jnp.concatenate([safe_rec, safe_rec])
        ev_begin = jnp.concatenate([tgt_begin, beg_s])
        ev_end = jnp.concatenate([tgt_end, end_s])
        ev_payload = jnp.concatenate([tgt_payload, data_s])
        ev_valid = jnp.concatenate([hit_live, drop_live])

    if with_audit:
        # lifecycle audit tap: one event slot per sorted placeholder for
        # each of {insert, eviction victim, overflow drop} — fixed [3N]
        # arrays, state 0 where masked. Victim rows carry the DESTROYED
        # version's window (gathered pre-scatter); drop rows carry the
        # never-inserted version's own window.
        ins_state = jnp.where(valid_s, AUDIT_COMMITTED, 0)
        vic_state = jnp.where(hit_live, AUDIT_OVERWROTE_LIVE,
                              jnp.where(hit_dead, AUDIT_OVERWROTE_DEAD, 0))
        drop_state = jnp.where(drop_live, AUDIT_OVERWROTE_LIVE,
                               jnp.where(dropped & ~drop_live,
                                         AUDIT_OVERWROTE_DEAD, 0))
        audit_arrays = {
            "audit_rec": jnp.concatenate([safe_rec, safe_rec, safe_rec]),
            "audit_begin": jnp.concatenate([beg_s, tgt_begin, beg_s]),
            "audit_end": jnp.concatenate([end_s, tgt_end, end_s]),
            "audit_state": jnp.concatenate(
                [ins_state, vic_state, drop_state]).astype(jnp.int32),
        }

    begin = begin.at[put_rec, slot].set(beg_s, mode="drop")
    end = end.at[put_rec, slot].set(end_s, mode="drop")

    inserted = jnp.zeros((R,), jnp.int32).at[
        jnp.where(w_valid, w_rec, R)].add(1, mode="drop")
    # both operands are non-negative (k_eff >= 1), so ``lax.rem`` is the
    # floored remainder without its sign fix-up
    head = jax.lax.rem(ring.head + jnp.minimum(inserted, k_arr), k_arr)

    new_ring = VersionRing(begin=begin, end=end, payload=payload, head=head)
    occ = ring_occupancy(new_ring)
    metrics = {
        "ring_evicted": evicted,
        "ring_overflow_dropped": jnp.sum(dropped),
        "ring_overwrote_live": jnp.sum(hit_live) + jnp.sum(drop_live),
        "ring_overwrote_dead": jnp.sum(hit_dead) + jnp.sum(
            dropped & ~drop_live),
        "ring_overwrote_rec": overwrote_rec + jnp.zeros(
            (R,), jnp.int32).at[jnp.where(drop_live, safe_rec, R)].add(
            1, mode="drop"),
        "ring_overwrote_dead_rec": overwrote_dead_rec + jnp.zeros(
            (R,), jnp.int32).at[jnp.where(dropped & ~drop_live, safe_rec,
                                          R)].add(1, mode="drop"),
        "ring_occ_max": jnp.max(occ),
        "ring_occ_mean": jnp.mean(occ.astype(jnp.float32)),
    }
    if with_evictees:
        metrics.update(evict_rec=ev_rec, evict_begin=ev_begin,
                       evict_end=ev_end, evict_payload=ev_payload,
                       evict_valid=ev_valid)
    if with_audit:
        metrics["ring_committed"] = jnp.sum(valid_s)
        metrics.update(audit_arrays)
    return new_ring, metrics


def gc_ring(ring: VersionRing, watermark: jax.Array
            ) -> Tuple[VersionRing, jax.Array]:
    """Standalone precise GC sweep: reclaim every version with
    ``end <= watermark`` (conditions 1+2 — no active or future reader can
    resolve inside a reclaimed version's [begin, end) window), touching
    nothing else. Returns (ring, evicted count).

    Reclamation is watermark-driven, not barrier-driven: ``commit_versions``
    runs this same condition as its step 1, but a merged CC epoch commits
    several admitted batches in ONE barrier and so skips the intermediate
    sweeps a batch-per-barrier schedule would have run. Those skipped
    sweeps only ever touch versions that are invisible to every legal
    reader — payloads are untouched and insertion is pure ring arithmetic
    — so the schedules differ transiently in which garbage slots are
    already marked empty, nothing more. A sweep at the CURRENT watermark
    (>= every watermark any prefix of the schedule used) erases exactly
    that difference: state after ``gc_ring(w)`` is a pure function of the
    committed history, whichever admission schedule produced it.
    """
    watermark = jnp.asarray(watermark, jnp.int32)
    live = ring.begin != INF_TS
    dead = live & (ring.end <= watermark)          # open versions: end==INF
    return VersionRing(begin=jnp.where(dead, INF_TS, ring.begin),
                       end=jnp.where(dead, INF_TS, ring.end),
                       payload=ring.payload,
                       head=ring.head), jnp.sum(dead)
