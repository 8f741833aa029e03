"""Secondary spill store: the overflow tier under the primary K-rings.

When a record's primary ring would overwrite a LIVE (reader-visible)
version — a hot record outrunning its K slots while a snapshot reader
still needs the history — the evicted version lands here instead of being
dropped, and the read path falls through primary -> spill
(``repro.store.sharded.resolve_sharded``), so historical reads that a
bare K-ring would answer ``found=False`` return real data.

Layout: a sparsely-allocated pool of version slots shared across records,
hash-indexed by record id.  ``num_buckets`` buckets of ``num_slots`` slots
each; record ``r`` (shard-local id) spills into bucket ``r % num_buckets``
and reads gather that whole bucket as the candidate window for the masked
resolve kernel (``mvcc_resolve_masked`` filters ``rec == r`` inside the
visibility test):

    begin   [B, S] i32   version begin ts (INF_TS = free slot)
    end     [B, S] i32   version end ts (spilled versions are always
                         closed — open heads are never evicted)
    rec     [B, S] i32   owning record id (-1 = free)
    payload [B, S, D]

Liveness is PIN-PRECISE (see ``pin_stabbed`` in repro/store/ring.py): a
version is spilled only when a registered snapshot pin lands inside its
[begin, end) window (or its end timestamp still reaches future readers).
That bounds spill occupancy by #pins x #records — one visible version per
(pin, record) pair — instead of the whole superseded history of every hot
key, which is what makes a small shared pool sufficient.

Allocation is deterministic and stateless: per commit, evictees are placed
newest-first into each bucket's slots in victim order — free slots first,
then occupied-but-unpinned slots oldest-first, then pinned slots oldest-
first (pinned history is overwritten LAST).  Reclamation follows the same
watermark rule as the primary ring: a sweep frees every slot with
``end <= watermark``, so once all pins release, one ``gc_sweep`` drains
the pool back to its initial (all-free, zeroed) state.
"""
from __future__ import annotations

import dataclasses
from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.kernels import ops
from repro.store.ring import INF_TS, pin_stabbed


@jax.tree_util.register_dataclass
@dataclasses.dataclass(frozen=True)
class SpillPool:
    begin: jax.Array     # [B, S] i32, INF_TS = free
    end: jax.Array       # [B, S] i32
    rec: jax.Array       # [B, S] i32, -1 = free (shard-local record id)
    payload: jax.Array   # [B, S, D]

    @property
    def num_buckets(self) -> int:
        return self.begin.shape[0]

    @property
    def num_slots(self) -> int:
        return self.begin.shape[1]


def init_spill_pool(num_buckets: int, num_slots: int, payload_words: int,
                    dtype=jnp.int32) -> SpillPool:
    """All-free pool (zeroed payloads — the state a full drain restores)."""
    B, S = int(num_buckets), int(num_slots)
    return SpillPool(
        begin=jnp.full((B, S), INF_TS, jnp.int32),
        end=jnp.full((B, S), INF_TS, jnp.int32),
        rec=jnp.full((B, S), -1, jnp.int32),
        payload=jnp.zeros((B, S, payload_words), dtype))


def spill_occupancy(pool: SpillPool) -> jax.Array:
    """[] occupied slot count."""
    return jnp.sum(pool.rec >= 0).astype(jnp.int32)


def spill_fill_fraction(pool: SpillPool) -> jax.Array:
    """[] occupied fraction of the pool in [0, 1] — the saturation gauge
    the obs layer surfaces (a full pool means live evictions start
    overwriting pinned history / dropping, i.e. found=False exposure)."""
    cap = pool.num_buckets * pool.num_slots
    return spill_occupancy(pool) / jnp.float32(max(cap, 1))


def spill_buckets_for(records: jax.Array, num_buckets: int) -> jax.Array:
    """Bucket index of each (shard-local) record id — the one home of the
    spill hash so commit and resolve can never disagree."""
    return jnp.maximum(records, 0) % num_buckets


def gc_spill(pool: SpillPool, watermark: jax.Array
             ) -> Tuple[SpillPool, jax.Array]:
    """Watermark sweep (GC conditions 1+2, same rule as ``gc_ring``):
    free every slot with ``end <= watermark``.  Freed slots are fully
    zeroed so the sweep is idempotent at the byte level and a drained
    pool is bit-identical to ``init_spill_pool``."""
    watermark = jnp.asarray(watermark, jnp.int32)
    dead = (pool.rec >= 0) & (pool.end <= watermark)
    return SpillPool(
        begin=jnp.where(dead, INF_TS, pool.begin),
        end=jnp.where(dead, INF_TS, pool.end),
        rec=jnp.where(dead, -1, pool.rec),
        payload=jnp.where(dead[..., None], 0, pool.payload),
    ), jnp.sum(dead)


def spill_commit(pool: SpillPool, ev_rec: jax.Array, ev_begin: jax.Array,
                 ev_end: jax.Array, ev_payload: jax.Array,
                 ev_valid: jax.Array, watermark: jax.Array,
                 pin_ts: Optional[jax.Array] = None,
                 with_audit: bool = False
                 ) -> Tuple[SpillPool, Dict[str, jax.Array]]:
    """Absorb one commit's live evictees into the pool.

    ``ev_*`` are the primary ring's evictee arrays ([Ne], ``ev_valid``
    masks the live ones — see ``commit_versions(..., with_evictees=True)``).
    Steps: (1) free dead slots at the watermark, (2) place evictees
    newest-first per bucket into victim-ordered slots (free slots first,
    pinned last), (3) report what was absorbed, overwritten and dropped.

    Everything is a fixed-shape sort/scatter, so the same code runs under
    vmap (logical shards) and shard_map (the ``cc`` mesh axis) unchanged.
    """
    B, S = pool.begin.shape
    watermark = jnp.asarray(watermark, jnp.int32)

    # -- 1. free dead slots so this commit's evictees can land ------------
    pool, freed = gc_spill(pool, watermark)

    # -- 2. bucket-major, newest-first evictee order ----------------------
    # (two stable argsorts emulate a lexsort without 64-bit keys; invalid
    # entries get bucket B and sort last)
    bkt = jnp.where(ev_valid, spill_buckets_for(ev_rec, B), B)
    newest_first = jnp.argsort(
        jnp.uint32(0xFFFFFFFF) - ev_begin.astype(jnp.uint32), stable=True)
    by_bucket = jnp.argsort(bkt[newest_first], stable=True)
    order = newest_first[by_bucket]
    bkt_s = bkt[order]
    valid_s = ev_valid[order]
    left = jnp.searchsorted(bkt_s, bkt_s, side="left")
    rank = (jnp.arange(bkt_s.shape[0]) - left).astype(jnp.int32)

    # -- victim order per bucket: free, then unpinned (oldest first),
    #    then pinned (oldest first) — pinned history dies last ------------
    occupied = pool.rec >= 0
    pinned = occupied & pin_stabbed(pool.begin, pool.end, pin_ts)
    prio = jnp.where(~occupied, 0, jnp.where(~pinned, 1, 2))
    by_begin = jnp.argsort(
        jnp.where(occupied, pool.begin, 0).astype(jnp.uint32),
        axis=1, stable=True)
    by_prio = jnp.argsort(jnp.take_along_axis(prio, by_begin, axis=1),
                          axis=1, stable=True)
    victim_order = jnp.take_along_axis(by_begin, by_prio, axis=1)  # [B, S]

    # -- 3. place: evictee with in-bucket rank r -> victim_order[bkt, r] --
    # the pool is donated to the commit and written in place: its headers
    # are read and scattered by (bucket, slot) pairs in their stored
    # [B, S] shape, its payload rows through the in-place row kernel.
    # Dropped entries read the last slot and write to bucket B, out of
    # range.
    placed = valid_s & (rank < S)
    slot = victim_order[jnp.minimum(bkt_s, B - 1), jnp.minimum(rank, S - 1)]
    put_bkt = jnp.where(placed, jnp.minimum(bkt_s, B - 1), B)
    get_bkt = jnp.where(placed, put_bkt, B - 1)
    get_slot = jnp.where(placed, slot, S - 1)
    v_rec = pool.rec[get_bkt, get_slot]
    v_begin = pool.begin[get_bkt, get_slot]
    v_end = pool.end[get_bkt, get_slot]
    victim_occ = placed & (v_rec >= 0)
    victim_pinned = placed & pinned[get_bkt, get_slot]

    def scatter(dst, src):
        return dst.at[put_bkt, slot].set(src, mode="drop")

    payload, _ = ops.update_rows(pool.payload, jnp.minimum(bkt_s, B - 1),
                                 slot, placed, ev_payload[order],
                                 with_old=False)
    new_pool = SpillPool(
        begin=scatter(pool.begin, ev_begin[order]),
        end=scatter(pool.end, ev_end[order]),
        rec=scatter(pool.rec, ev_rec[order]),
        payload=payload)

    metrics = {
        "spill_freed": freed,
        "spill_admitted": jnp.sum(placed),
        "spill_dropped": jnp.sum(valid_s & ~placed),
        "spill_overwrote": jnp.sum(victim_occ),
        "spill_overwrote_pinned": jnp.sum(victim_pinned),
        "spill_occupancy": spill_occupancy(new_pool),
    }
    if with_audit:
        # lifecycle audit tap: per-evictee placement outcome plus the
        # (rec, begin, end) of any spill-resident version this placement
        # destroyed — both scattered back to INPUT order so the caller
        # can pair them with its own ``ev_*`` arrays.
        Ne = ev_rec.shape[0]

        def to_input(sorted_vals, fill):
            init = jnp.full((Ne,), fill, sorted_vals.dtype)
            return init.at[order].set(sorted_vals)

        metrics.update(
            spill_audit_placed=to_input(placed, False),
            spill_victim_valid=to_input(victim_occ, False),
            spill_victim_rec=to_input(jnp.where(victim_occ, v_rec, -1), -1),
            spill_victim_begin=to_input(
                jnp.where(victim_occ, v_begin, INF_TS), INF_TS),
            spill_victim_end=to_input(
                jnp.where(victim_occ, v_end, INF_TS), INF_TS),
        )
    return new_pool, metrics
