"""Paged physical version storage: a page-slab allocator for the rings.

The dense primary store allocates every record's ring at the physical
slot ceiling ``k_max`` — ``adaptive_k`` is a *logical* cap, so a store
sized for millions of records pays worst-case memory for its coldest
tail. This module replaces the dense ``[R, K]`` ring arrays with

    begin      [P, S] i32    slab: page-major version slots (INF = empty)
    end        [P, S] i32
    payload    [P, S, D]
    page_table [R, MaxP] i32 per-record page ids (-1 = unmapped)
    head       [R]    i32    logical insert cursor (mod k_eff, as dense)

where ``P`` (the slab page count) is a real physical budget: a cold
record holds ONE page (its initial version) instead of ``k_max`` slots,
and hot records grow by whole pages granted from a free list. The same
design already carries the serving KV cache (``repro.serving.pages``);
this is the transaction-store instance of it.

The LOGICAL semantics are exactly the dense ring's: record ``r`` owns
logical slots ``[0, MaxP * S)``; insertion is ring arithmetic
``(head + rank) % k_eff`` over logical slots; a logical slot ``j`` is
backed by physical slot ``page_table[r, j // S] * S + j % S``. Because
the logical slot space, insertion order, overwrite targets and GC rule
are identical, a paged store answers every read byte-identically to a
dense ring store with the same ``k_eff`` trajectory (property-tested in
tests/test_pages.py) — the only new loss mode is free-list exhaustion,
which drops the unplaceable versions (counted, offered to spill, and a
later read reports ``found=False``, never a stale payload).

Page allocation is deterministic and stateless, the same idiom as
``spill_commit``'s victim ordering: per commit, page requests (record,
page-index) in row-major order are matched against the free list (pages
referenced by no table entry) in ascending page-id order — one cumsum +
one stable argsort, no allocator state to carry or replay.

Reclamation is two-level: the watermark sweep frees SLOTS (same
``end <= watermark`` rule as the dense ring, §4.2.2 conditions 1+2,
freed slots fully zeroed), and ``gc_pages`` additionally returns whole
pages to the free list when every slot is free AND the page sits beyond
the record's current capacity ``ceil(k_eff / S)`` — the pages a policy
shrink stranded. Capacity itself moves at page granularity: the
adaptive-K policy runs with ``quantum = S`` (see repro/store/policy.py),
so ``reassign_k`` is a physical page grant/reclaim, not a logical cap.
"""
from __future__ import annotations

import dataclasses
from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.store.ring import (AUDIT_COMMITTED, AUDIT_OVERWROTE_DEAD,
                              AUDIT_OVERWROTE_LIVE, AUDIT_PAGE_DROPPED,
                              INF_TS, pin_stabbed)


@jax.tree_util.register_dataclass
@dataclasses.dataclass(frozen=True)
class PageSlab:
    begin: jax.Array       # [P, S] i32, INF_TS = empty slot
    end: jax.Array         # [P, S] i32
    payload: jax.Array     # [P, S, D]
    page_table: jax.Array  # [R, MaxP] i32 page ids, -1 = unmapped
    head: jax.Array        # [R] i32 logical insert cursor

    # negative indices: the same properties read correctly on a stacked
    # [n, ...] slab (repro.store.sharded) and on one shard's slab
    @property
    def num_pages(self) -> int:
        return self.begin.shape[-2]

    @property
    def page_slots(self) -> int:
        return self.begin.shape[-1]

    @property
    def num_records(self) -> int:
        return self.page_table.shape[-2]

    @property
    def max_pages(self) -> int:
        return self.page_table.shape[-1]

    @property
    def num_slots(self) -> int:
        """Logical slot ceiling per record (the dense store's K)."""
        return self.max_pages * self.page_slots


def init_page_slab(base: jax.Array, base_ts: jax.Array, real: jax.Array,
                   num_pages: int, page_slots: int,
                   max_pages: int) -> PageSlab:
    """One shard's slab: real record ``r`` maps page ``r`` whose slot 0
    holds the initial open version (hash-padding records map nothing).
    Requires ``num_pages >= num_records`` — every live record needs at
    least its initial page."""
    R, D = base.shape
    P, S = int(num_pages), int(page_slots)
    if P < R:
        raise ValueError("pages_per_shard must be >= records per shard "
                         "(each record holds at least its initial page)")
    real = jnp.asarray(real, bool)
    begin = jnp.full((P, S), INF_TS, jnp.int32)
    begin = begin.at[:R, 0].set(
        jnp.where(real, jnp.asarray(base_ts, jnp.int32), INF_TS))
    end = jnp.full((P, S), INF_TS, jnp.int32)
    payload = jnp.zeros((P, S, D), base.dtype)
    payload = payload.at[:R, 0, :].set(jnp.where(real[:, None], base, 0))
    page_table = jnp.full((R, int(max_pages)), -1, jnp.int32)
    page_table = page_table.at[:, 0].set(
        jnp.where(real, jnp.arange(R, dtype=jnp.int32), -1))
    head = jnp.full((R,), 1 % (int(max_pages) * S), jnp.int32)
    return PageSlab(begin=begin, end=end, payload=payload,
                    page_table=page_table, head=head)


def page_owner_index(page_table: jax.Array, num_pages: int
                     ) -> Tuple[jax.Array, jax.Array]:
    """Invert the page table: (owner [P] record id or -1, pidx [P] the
    page's index within its owner's table). The table is the single
    source of truth — ownership is always derived, never stored."""
    R, MaxP = page_table.shape
    pt = page_table.reshape(-1)
    rec = jnp.broadcast_to(jnp.arange(R, dtype=jnp.int32)[:, None],
                           (R, MaxP)).reshape(-1)
    idx = jnp.broadcast_to(jnp.arange(MaxP, dtype=jnp.int32)[None, :],
                           (R, MaxP)).reshape(-1)
    tgt = jnp.where(pt >= 0, pt, num_pages)
    owner = jnp.full((num_pages,), -1, jnp.int32).at[tgt].set(
        rec, mode="drop")
    pidx = jnp.full((num_pages,), -1, jnp.int32).at[tgt].set(
        idx, mode="drop")
    return owner, pidx


def mapped_page_count(slab: PageSlab) -> jax.Array:
    """[] number of pages currently referenced by the page table."""
    return jnp.sum(slab.page_table >= 0).astype(jnp.int32)


def free_page_count(slab: PageSlab) -> jax.Array:
    """[] pages available to the allocator."""
    return jnp.int32(slab.num_pages) - mapped_page_count(slab)


def slab_fill_fraction(slab: PageSlab) -> jax.Array:
    """[] mapped fraction of the slab in [0, 1] — the allocator
    saturation gauge (at 1.0 the free list is empty and further version
    placements fail, degrading historical reads to found=False)."""
    return mapped_page_count(slab) / jnp.float32(max(slab.num_pages, 1))


def paged_occupancy(slab: PageSlab) -> jax.Array:
    """[R] live (non-garbage) version count per record — the paged twin
    of ``ring_occupancy``."""
    owner, _ = page_owner_index(slab.page_table, slab.num_pages)
    per_page = jnp.sum(slab.begin != INF_TS, axis=1).astype(jnp.int32)
    R = slab.num_records
    return jnp.zeros((R,), jnp.int32).at[
        jnp.where(owner >= 0, owner, R)].add(per_page, mode="drop")


def mask_gathered_windows(pt: jax.Array, begin_g: jax.Array,
                          end_g: jax.Array, payload_g: jax.Array
                          ) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """Turn per-read gathered page windows into flat dense-shaped
    candidate windows: pt [B, MaxP] (the rows the gather used, -1 =
    unmapped), begin_g/end_g [B, MaxP, S], payload_g [B, MaxP, S, D] ->
    (begin [B, MaxP*S], end, payload [B, MaxP*S, D]) with unmapped
    pages' slots emptied. One home for the unmapped-fill rule — the
    single-shard and cross-shard gathers both finish here."""
    mapped = (pt >= 0)[..., None]                      # [B, MaxP, 1]
    B, MaxP = pt.shape
    S = begin_g.shape[-1]
    begin = jnp.where(mapped, begin_g, INF_TS)
    end = jnp.where(mapped, end_g, INF_TS)
    payload = jnp.where(mapped[..., None], payload_g, 0)
    return (begin.reshape(B, MaxP * S), end.reshape(B, MaxP * S),
            payload.reshape(B, MaxP * S, -1))


def gather_windows_paged(slab: PageSlab, records: jax.Array
                         ) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """Materialise per-read candidate windows through the page table:
    records [B] -> (begin [B, MaxP*S], end, payload [B, MaxP*S, D]) —
    the paged store's input to the ``mvcc_resolve`` kernel."""
    rec = jnp.maximum(jnp.asarray(records, jnp.int32), 0)
    pt = slab.page_table[rec]                          # [B, MaxP]
    safe = jnp.maximum(pt, 0)
    return mask_gathered_windows(pt, slab.begin[safe], slab.end[safe],
                                 slab.payload[safe])


def commit_paged(slab: PageSlab, w_rec: jax.Array, w_key: jax.Array,
                 w_valid: jax.Array, w_begin_ts: jax.Array,
                 w_end_ts: jax.Array, w_data: jax.Array,
                 watermark: jax.Array,
                 ts_window: Optional[Tuple[jax.Array, jax.Array]] = None,
                 k_eff: Optional[jax.Array] = None,
                 pin_ts: Optional[jax.Array] = None,
                 with_evictees: bool = False,
                 with_audit: bool = False
                 ) -> Tuple[PageSlab, Dict[str, jax.Array]]:
    """The paged twin of ``commit_versions`` — same contract, same
    metrics keys (so the sharded aggregation and the engine's pressure
    accounting run unchanged), plus the allocator's own counters:

      1. reclaim every version with end <= (clamped) watermark;
      2. close the previously-open head version of each written record;
      3. insert at logical ring positions (head + rank) % k_eff,
         allocating pages from the free list for logical pages the
         record does not map yet (deterministic: requests in (record,
         page-index) order take free pages in ascending id order).

    A version whose page request cannot be satisfied (slab exhausted) is
    dropped exactly like a within-batch ring overflow: counted under
    ``paged_alloc_failed``, its liveness assessed pin-precisely, and —
    when ``with_evictees`` — offered to the spill tier, so a saturated
    slab degrades to found=False reads, never stale ones.
    """
    P, S = slab.begin.shape
    R, MaxP = slab.page_table.shape
    watermark = jnp.asarray(watermark, jnp.int32)
    if ts_window is not None:
        watermark = jnp.minimum(watermark,
                                jnp.asarray(ts_window[0], jnp.int32))
    k_arr = (jnp.full((R,), MaxP * S, jnp.int32) if k_eff is None
             else jnp.asarray(k_eff, jnp.int32))
    floor = (jnp.asarray(ts_window[1], jnp.int32) - 1
             if ts_window is not None else watermark)

    # -- 1. precise reclamation below the watermark (slab-wide; freed
    #       slots fully zeroed so a drained page is byte-identical free) -
    live = slab.begin != INF_TS
    dead = live & (slab.end <= watermark)
    evicted = jnp.sum(dead)
    begin = jnp.where(dead, INF_TS, slab.begin)
    end = jnp.where(dead, INF_TS, slab.end)
    payload = jnp.where(dead[..., None], 0, slab.payload)

    # -- 2. close the open head version of every written record ------------
    first_ts = jnp.full((R,), INF_TS, jnp.int32).at[
        jnp.where(w_valid, w_rec, R)].min(
        jnp.where(w_valid, w_begin_ts, INF_TS), mode="drop")
    owner, _ = page_owner_index(slab.page_table, P)
    ft_page = jnp.where(owner >= 0,
                        first_ts[jnp.clip(owner, 0, R - 1)], INF_TS)
    open_slot = (end == INF_TS) & (begin != INF_TS)
    end = jnp.where(open_slot & (ft_page != INF_TS)[:, None],
                    ft_page[:, None], end)

    # -- 3. insert at logical ring positions -------------------------------
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
    k_rec = k_arr[safe_rec]
    drop_n = jnp.maximum(count - k_rec, 0)         # overflow: drop oldest
    keep = valid_s & (rank >= drop_n)
    lslot = (slab.head[safe_rec] + rank - drop_n) % k_rec   # logical slot
    lpage = jnp.minimum(lslot // S, MaxP - 1)      # page index (in-bound
    #                                                when k_eff <= MaxP*S)

    # -- page allocation: free-list as a sorted index pass -----------------
    # requests = (record, page-index) cells some kept insert lands in and
    # the table does not map; the q-th request (row-major table order)
    # takes the q-th free page (ascending id) — stateless and replayable
    need = keep & (slab.page_table[safe_rec, lpage] < 0)
    req = jnp.zeros((R, MaxP), bool).at[
        jnp.where(need, safe_rec, R), lpage].set(True, mode="drop")
    pt_flat = slab.page_table.reshape(-1)
    used = jnp.zeros((P,), bool).at[
        jnp.where(pt_flat >= 0, pt_flat, P)].set(True, mode="drop")
    n_free = jnp.sum(~used)
    # free pages first, ascending id (uint32 keys — the jax-floor-safe
    # idiom the spill allocator uses for its stable argsorts)
    free_ids = jnp.argsort(used.astype(jnp.uint32), stable=True)
    req_flat = req.reshape(-1)
    req_rank = jnp.cumsum(req_flat) - 1
    granted = req_flat & (req_rank < n_free)
    grant_page = jnp.where(
        granted, free_ids[jnp.clip(req_rank, 0, P - 1)], -1)
    page_table = jnp.where(granted.reshape(R, MaxP),
                           grant_page.reshape(R, MaxP).astype(jnp.int32),
                           slab.page_table)

    pid = page_table[safe_rec, lpage]
    landed = keep & (pid >= 0)
    flat = jnp.where(landed, pid * S + lslot % S, P * S)   # OOB => dropped
    safe_flat = jnp.minimum(flat, P * S - 1)
    tgt_begin = begin.reshape(-1)[safe_flat]
    tgt_end = end.reshape(-1)[safe_flat]
    # liveness of what this insert destroys: pin-precise, as the dense
    # ring (see repro/store/ring.py)
    hit_any = landed & (tgt_begin != INF_TS)
    tgt_live = (tgt_end > floor) | pin_stabbed(tgt_begin, tgt_end, pin_ts)
    hit_live = hit_any & tgt_live
    hit_dead = hit_any & ~tgt_live
    overwrote_rec = jnp.zeros((R,), jnp.int32).at[
        jnp.where(hit_live, safe_rec, R)].add(1, mode="drop")
    overwrote_dead_rec = jnp.zeros((R,), jnp.int32).at[
        jnp.where(hit_dead, safe_rec, R)].add(1, mode="drop")

    # never-inserted versions (ring overflow + allocation failures) face
    # the same pin-precise liveness test
    dropped = valid_s & ~landed
    drop_live = dropped & ((end_s > floor) | pin_stabbed(beg_s, end_s,
                                                         pin_ts))

    if with_evictees:
        tgt_payload = payload.reshape(P * S, -1)[safe_flat]
        ev_rec = jnp.concatenate([safe_rec, safe_rec])
        ev_begin = jnp.concatenate([tgt_begin, beg_s])
        ev_end = jnp.concatenate([tgt_end, end_s])
        ev_payload = jnp.concatenate([tgt_payload, data_s])
        ev_valid = jnp.concatenate([hit_live, drop_live])

    if with_audit:
        # lifecycle audit tap — as the dense ring, except a drop caused
        # by free-list exhaustion (kept by the ring rule but no page to
        # land in) is stamped PAGE_DROPPED: the allocator, not K-overflow,
        # destroyed it.
        alloc_fail = keep & ~landed
        ins_state = jnp.where(valid_s, AUDIT_COMMITTED, 0)
        vic_state = jnp.where(hit_live, AUDIT_OVERWROTE_LIVE,
                              jnp.where(hit_dead, AUDIT_OVERWROTE_DEAD, 0))
        drop_state = jnp.where(
            alloc_fail, AUDIT_PAGE_DROPPED,
            jnp.where(drop_live & ~alloc_fail, AUDIT_OVERWROTE_LIVE,
                      jnp.where(dropped & ~drop_live & ~alloc_fail,
                                AUDIT_OVERWROTE_DEAD, 0)))
        audit_arrays = {
            "audit_rec": jnp.concatenate([safe_rec, safe_rec, safe_rec]),
            "audit_begin": jnp.concatenate([beg_s, tgt_begin, beg_s]),
            "audit_end": jnp.concatenate([end_s, tgt_end, end_s]),
            "audit_state": jnp.concatenate(
                [ins_state, vic_state, drop_state]).astype(jnp.int32),
        }

    begin = begin.reshape(-1).at[flat].set(beg_s, mode="drop").reshape(P, S)
    end = end.reshape(-1).at[flat].set(end_s, mode="drop").reshape(P, S)
    payload = payload.reshape(P * S, -1).at[flat].set(
        data_s, mode="drop").reshape(slab.payload.shape)

    inserted = jnp.zeros((R,), jnp.int32).at[
        jnp.where(w_valid, w_rec, R)].add(1, mode="drop")
    head = (slab.head + jnp.minimum(inserted, k_arr)) % k_arr

    new_slab = PageSlab(begin=begin, end=end, payload=payload,
                        page_table=page_table, head=head)
    occ = paged_occupancy(new_slab)
    metrics = {
        "ring_evicted": evicted,
        "ring_overflow_dropped": jnp.sum(valid_s & ~keep),
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
        "paged_alloc_failed": jnp.sum(keep & ~landed),
        "paged_pages_allocated": jnp.sum(granted),
        "paged_pages_free": n_free.astype(jnp.int32)
        - jnp.sum(granted).astype(jnp.int32),
    }
    if with_evictees:
        metrics.update(evict_rec=ev_rec, evict_begin=ev_begin,
                       evict_end=ev_end, evict_payload=ev_payload,
                       evict_valid=ev_valid)
    if with_audit:
        metrics["ring_committed"] = jnp.sum(valid_s)
        metrics.update(audit_arrays)
    return new_slab, metrics


def gc_pages(slab: PageSlab, watermark: jax.Array, k_eff: jax.Array
             ) -> Tuple[PageSlab, jax.Array]:
    """Two-level standalone sweep: free every SLOT with ``end <=
    watermark`` (conditions 1+2, freed slots fully zeroed), then return
    to the free list every PAGE that is (a) fully free and (b) beyond
    its owner's current capacity ``ceil(k_eff / S)`` — the pages a
    policy shrink stranded, now drained. Pages inside the capacity
    window stay mapped even when momentarily empty (the next insert
    would only re-request them). Returns (slab, freed version count) —
    the count matches the dense ``gc_ring`` exactly, page returns are a
    physical-layout event with no logical content."""
    watermark = jnp.asarray(watermark, jnp.int32)
    S = slab.page_slots
    live = slab.begin != INF_TS
    dead = live & (slab.end <= watermark)
    begin = jnp.where(dead, INF_TS, slab.begin)
    end = jnp.where(dead, INF_TS, slab.end)
    payload = jnp.where(dead[..., None], 0, slab.payload)

    owner, pidx = page_owner_index(slab.page_table, slab.num_pages)
    empty = jnp.all(begin == INF_TS, axis=1)                   # [P]
    k = jnp.asarray(k_eff, jnp.int32)
    pages_needed = -(-k // S)                                  # ceil
    stranded = (owner >= 0) & empty & (
        pidx >= pages_needed[jnp.clip(owner, 0, slab.num_records - 1)])
    # unmap: a table entry is cleared exactly when its page is stranded
    strand_pos = (slab.page_table >= 0) & stranded[
        jnp.clip(slab.page_table, 0, slab.num_pages - 1)]
    page_table = jnp.where(strand_pos, -1, slab.page_table)
    return PageSlab(begin=begin, end=end, payload=payload,
                    page_table=page_table, head=slab.head), jnp.sum(dead)
