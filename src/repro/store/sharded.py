"""Record-partitioned version store: the ring sharded over the ``cc`` axis.

``ShardedVersionStore`` partitions the persistent version ring by record
hash — global record ``r`` is owned by shard ``r % n`` at local index
``r // n``, the same ownership rule as the record-partitioned CC planner
(``cc_plan_sharded``) — so commit, watermark GC and snapshot resolution
all run per shard without ever materialising a global [R, K] store:

  * ``commit_sharded``  each shard masks the batch's placeholder arrays to
    the records it owns and runs the single-ring ``commit_versions`` on
    its local ring — zero cross-shard communication (commit order inside
    a record segment is a per-record property, and every record has
    exactly one owner). When the store carries a spill tier, each shard
    feeds its own live evictees straight into its local spill pool
    (``repro.store.spill``) inside the same per-shard body;
  * ``resolve_sharded``  each shard gathers candidate windows for the
    reads it owns and resolves visibility through the ``mvcc_resolve``
    Pallas kernel, falling through primary -> spill (the masked kernel
    filters the shared spill buckets by record id); per-read results
    merge by ownership (each read has exactly one owner, others
    contribute zeros);
  * GC is watermark-driven per shard — the watermark is a global scalar,
    so reclamation decisions (rings AND spill) are embarrassingly
    parallel.

Two mapping substrates share one per-shard body:

  * ``mesh`` given (a ``cc`` axis with n devices): ``shard_map`` — each
    device holds one shard's ring + spill arrays and commits/resolves
    locally;
  * no mesh: logical shards on one device (vmap for commit, an unrolled
    loop of kernel calls for resolve) — the layout and arithmetic are
    identical, so sharded state is bit-equal across substrates.

The commit maps its per-shard body over the stacked leading axis with
``vmap`` on every substrate, one shard included, so each leaf is read and
written in its stored ``[n, ...]`` shape and the donated store is updated
in place. The read paths short-circuit ``n_shards == 1`` to the plain
single-ring code on the squeezed arrays — bit-identical to the unsharded
store.
"""
from __future__ import annotations

import dataclasses
from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.kernels import ops
from repro.store.pages import (PageSlab, commit_paged, gather_windows_paged,
                               gc_pages, init_page_slab,
                               mask_gathered_windows, page_owner_index,
                               paged_occupancy, slab_fill_fraction)
from repro.store.ring import (AUDIT_SPILL_DROPPED, AUDIT_SPILL_OVERWROTE,
                              AUDIT_SPILLED, INF_TS, VersionRing,
                              commit_versions, gather_windows, gc_ring,
                              pin_stabbed, ring_occupancy)
from repro.store.spill import (SpillPool, gc_spill, init_spill_pool,
                               spill_buckets_for, spill_commit,
                               spill_fill_fraction, spill_occupancy)

PAD_KEY = jnp.uint32(0xFFFFFFFF)

_EVICT_KEYS = ("evict_rec", "evict_begin", "evict_end", "evict_payload",
               "evict_valid")


@dataclasses.dataclass(frozen=True)
class ShardedVersionStore:
    """Primary version storage + spill pools stacked over a leading
    shard axis.

    The primary level is EITHER ``rings`` (dense [n, Rl, K] per-record
    rings) OR ``pages`` (a paged slab [n, P, S] + page table
    [n, Rl, MaxP] — see ``repro.store.pages``); exactly one is set.
    ``R_local = ceil(num_records / n)``; records past ``num_records``
    (hash-padding) hold empty rings / no pages and are never read or
    written. ``spill`` (optional) holds each shard's secondary version
    pool — live evictions from the primary land there and the resolve
    path falls through to it. ``k_eff`` [n, R_local] is each record's
    effective primary capacity (adaptive K; insertion-only — resolution
    and GC always scan all physical slots).
    """
    rings: Optional[VersionRing]  # stacked: begin/end [n, Rl, K] or None
    spill: Optional[SpillPool]   # stacked [n, B, S, ...] or None
    k_eff: jax.Array         # [n, Rl] i32 per-record ring capacity
    num_records: int         # global record count (static)
    pages: Optional[PageSlab] = None   # stacked [n, P, S, ...] or None

    @property
    def paged(self) -> bool:
        return self.pages is not None

    @property
    def n_shards(self) -> int:
        return (self.rings.begin if self.rings is not None
                else self.pages.page_table).shape[0]

    @property
    def records_per_shard(self) -> int:
        return (self.rings.begin if self.rings is not None
                else self.pages.page_table).shape[1]

    @property
    def num_slots(self) -> int:
        """Logical slot ceiling per record (dense K, or MaxP * S)."""
        if self.rings is not None:
            return self.rings.begin.shape[2]
        return self.pages.page_table.shape[2] * self.pages.begin.shape[2]


jax.tree_util.register_dataclass(
    ShardedVersionStore, data_fields=("rings", "spill", "k_eff", "pages"),
    meta_fields=("num_records",))


def _primary(store: ShardedVersionStore):
    """The stacked primary level: rings or pages (exactly one is set)."""
    return store.rings if store.rings is not None else store.pages


def _with_primary(store: ShardedVersionStore, prim):
    if store.rings is not None:
        return dataclasses.replace(store, rings=prim)
    return dataclasses.replace(store, pages=prim)


def _ring0(store: ShardedVersionStore):
    """The squeezed single primary of an n_shards == 1 store, for the read
    paths (the commit never squeezes: it maps over the shard axis)."""
    return jax.tree.map(lambda x: x[0], _primary(store))


def _take_shard(store: ShardedVersionStore, s: int):
    return jax.tree.map(lambda x: x[s], _primary(store))


def _take_spill(store: ShardedVersionStore, s) -> Optional[SpillPool]:
    if store.spill is None:
        return None
    return jax.tree.map(lambda x: x[s], store.spill)


def init_sharded_store(base: jax.Array, base_ts: Optional[jax.Array] = None,
                       num_slots: int = 4,
                       n_shards: int = 1,
                       spill_buckets: int = 0,
                       spill_slots: int = 0,
                       k_init: Optional[int] = None,
                       paged: bool = False,
                       page_slots: int = 4,
                       pages_per_shard: Optional[int] = None
                       ) -> ShardedVersionStore:
    """Store whose slot 0 holds the initial open version of every record,
    hash-partitioned into ``n_shards`` rings.  ``spill_buckets`` x
    ``spill_slots`` > 0 attaches a per-shard spill pool; ``k_init`` caps
    each record's effective ring capacity below the physical
    ``num_slots`` (the adaptive-K starting point).

    ``paged=True`` replaces the dense [Rl, K] rings with a per-shard
    page slab (``repro.store.pages``): ``pages_per_shard`` pages of
    ``page_slots`` slots, page tables sized ``ceil(num_slots /
    page_slots)`` entries so a record can still reach ``num_slots``
    logical slots — but only the pages it actually uses are allocated
    (every real record starts with exactly its initial page)."""
    R, D = base.shape
    if base_ts is None:
        base_ts = jnp.zeros((R,), jnp.int32)
    n = int(n_shards)
    Rl = -(-R // n)
    pad = Rl * n - R
    basep = jnp.pad(jnp.asarray(base), ((0, pad), (0, 0)))
    tsp = jnp.pad(jnp.asarray(base_ts, jnp.int32), (0, pad))
    # global record r = local * n + shard lives at [shard, local]
    base_sh = basep.reshape(Rl, n, D).transpose(1, 0, 2)
    ts_sh = tsp.reshape(Rl, n).T
    real = global_record_ids(n, Rl) < R                       # [n, Rl]
    rings = pages = None
    if paged:
        max_pages = -(-int(num_slots) // int(page_slots))
        if pages_per_shard is None:
            # per-record ceiling, NOT the pooled slot budget: when the
            # capacity is not a page multiple every record still needs
            # ceil(k / S) whole pages to physically reach its k_eff
            pages_per_shard = Rl * -(-int(k_init or num_slots)
                                     // int(page_slots))
        pages = jax.vmap(
            lambda b, ts, re: init_page_slab(b, ts, re, pages_per_shard,
                                             page_slots, max_pages)
        )(base_sh, ts_sh, real)
    else:
        begin = jnp.full((n, Rl, num_slots), INF_TS, jnp.int32)
        begin = begin.at[:, :, 0].set(jnp.where(real, ts_sh, INF_TS))
        end = jnp.full((n, Rl, num_slots), INF_TS, jnp.int32)
        payload = jnp.zeros((n, Rl, num_slots, D), basep.dtype)
        payload = payload.at[:, :, 0, :].set(
            jnp.where(real[..., None], base_sh, 0))
        head = jnp.full((n, Rl), 1 % num_slots, jnp.int32)
        rings = VersionRing(begin=begin, end=end, payload=payload,
                            head=head)
    spill = None
    if int(spill_buckets) > 0 and int(spill_slots) > 0:
        pool = init_spill_pool(spill_buckets, spill_slots, D, basep.dtype)
        spill = jax.tree.map(
            lambda x: jnp.broadcast_to(x[None], (n,) + x.shape), pool)
    k0 = num_slots if k_init is None else min(int(k_init), num_slots)
    return ShardedVersionStore(
        rings=rings, spill=spill,
        k_eff=jnp.full((n, Rl), k0, jnp.int32),
        num_records=R, pages=pages)


def global_record_ids(n_shards: int, records_per_shard: int) -> jax.Array:
    """[n, Rl] global record id at each sharded position."""
    local = jnp.arange(records_per_shard, dtype=jnp.int32)[None, :]
    shard = jnp.arange(n_shards, dtype=jnp.int32)[:, None]
    return local * n_shards + shard


def unshard(store: ShardedVersionStore) -> VersionRing:
    """Materialise the global [R, K] ring. Tests/debug only — no hot path
    calls this (the whole point of the sharded store)."""
    if store.rings is None:
        raise ValueError("unshard materialises dense rings; a paged "
                         "store has no global [R, K] layout — compare "
                         "reads (resolve_sharded) or use "
                         "gather_windows_sharded instead")
    n, Rl = store.n_shards, store.records_per_shard
    R = store.num_records

    def merge(x):
        return jnp.moveaxis(x, 0, 1).reshape((Rl * n,) + x.shape[2:])[:R]

    return jax.tree.map(merge, store.rings)


def to_global(store: ShardedVersionStore, per_shard: jax.Array) -> jax.Array:
    """Re-index a per-shard [n, Rl] record statistic to global [R]."""
    n, Rl = store.n_shards, store.records_per_shard
    return jnp.moveaxis(per_shard, 0, 1).reshape(
        (Rl * n,) + per_shard.shape[2:])[:store.num_records]


def from_global(store: ShardedVersionStore, per_record: jax.Array,
                pad_value: int = 0) -> jax.Array:
    """Inverse of ``to_global``: scatter a global [R] record statistic
    into the sharded [n, Rl] layout (hash-padding records get
    ``pad_value``)."""
    n, Rl = store.n_shards, store.records_per_shard
    per_record = jnp.asarray(per_record)
    pad = Rl * n - store.num_records
    padded = jnp.pad(per_record, [(0, pad)] + [(0, 0)] * (
        per_record.ndim - 1), constant_values=pad_value)
    return jnp.moveaxis(padded.reshape((Rl, n) + per_record.shape[1:]),
                        0, 1)


def store_occupancy(store: ShardedVersionStore) -> jax.Array:
    """[R] live version count per global record."""
    if store.rings is not None:
        return to_global(store, ring_occupancy(store.rings))
    return to_global(store, jax.vmap(paged_occupancy)(store.pages))


def store_health(store: ShardedVersionStore) -> Dict[str, jax.Array]:
    """Per-shard health gauges as LAZY device values — nothing here
    synchronises; the obs layer's single snapshot transfer (or an
    explicit ``health()`` call) realises the whole dict at once.

      live_versions [n]   live version count per shard
      k_eff_slots   [n]   effective (policy-granted) slot capacity
      pages_mapped / pages_free / slab_fill [n]  (paged stores)
      spill_occupancy / spill_fill [n]           (spill tier attached)
    """
    out: Dict[str, jax.Array] = {"k_eff_slots": jnp.sum(store.k_eff, -1)}
    if store.rings is not None:
        out["live_versions"] = jnp.sum(ring_occupancy(store.rings), -1)
    else:
        out["live_versions"] = jnp.sum(
            jax.vmap(paged_occupancy)(store.pages), -1)
        mapped = jnp.sum(store.pages.page_table >= 0, axis=(1, 2))
        out["pages_mapped"] = mapped.astype(jnp.int32)
        out["pages_free"] = (store.pages.num_pages
                             - mapped).astype(jnp.int32)
        out["slab_fill"] = jax.vmap(slab_fill_fraction)(store.pages)
    if store.spill is not None:
        out["spill_occupancy"] = jax.vmap(spill_occupancy)(store.spill)
        out["spill_fill"] = jax.vmap(spill_fill_fraction)(store.spill)
    return out


# ---------------------------------------------------------------------------
# Commit: per-shard ring maintenance (GC + insert + spill), no communication.
# ---------------------------------------------------------------------------
def _mask_to_shard(n: int, shard, w_rec, w_key, w_valid):
    """Project global placeholder arrays onto one shard: foreign records
    become pads (key UINT32_MAX sorts last, valid=False drops the write),
    owned records map to their shard-local index. The global (rec, ts) key
    order is preserved within a shard — rec -> rec // n is monotone over
    the records a shard owns — so the key needs no recomputation."""
    owned = w_valid & ((w_rec % n) == shard)
    rec_l = jnp.where(owned, w_rec // n, jnp.int32(INF_TS))
    key_l = jnp.where(owned, w_key, PAD_KEY)
    return rec_l, key_l, owned


def _commit_one_shard(ring_s, spill_s: Optional[SpillPool],
                      k_eff_s: jax.Array, rec_l, key_l, owned, w_begin_ts,
                      w_end_ts, w_data, watermark, ts_window, pin_ts,
                      with_audit: bool = False):
    """One shard's full commit: primary maintenance (dense ring or paged
    slab — same contract, dispatched on the pytree type), then its live
    evictees into the local spill pool (same clamped watermark).

    ``with_audit=True`` additionally emits fixed-shape lifecycle audit
    arrays (``audit_rec/begin/end/state``, shard-LOCAL record ids) — the
    primary's 3 event segments plus, when a spill pool is attached, the
    per-evictee placement outcome (SPILLED / SPILL_DROPPED) and the spill
    versions those placements destroyed (SPILL_OVERWROTE)."""
    with_spill = spill_s is not None
    commit_fn = commit_paged if isinstance(ring_s, PageSlab) \
        else commit_versions
    with jax.named_scope("commit/ring"):
        ring_o, m = commit_fn(ring_s, rec_l, key_l, owned, w_begin_ts,
                              w_end_ts, w_data, watermark,
                              ts_window=ts_window, k_eff=k_eff_s,
                              pin_ts=pin_ts, with_evictees=with_spill,
                              with_audit=with_audit)
    if with_spill:
        with jax.named_scope("commit/spill"):
            ev = {k: m.pop(k) for k in _EVICT_KEYS}
            wm = jnp.asarray(watermark, jnp.int32)
            if ts_window is not None:
                wm = jnp.minimum(wm, jnp.asarray(ts_window[0], jnp.int32))
            spill_s, sm = spill_commit(spill_s, ev["evict_rec"],
                                       ev["evict_begin"], ev["evict_end"],
                                       ev["evict_payload"], ev["evict_valid"],
                                       wm, pin_ts=pin_ts,
                                       with_audit=with_audit)
            if with_audit:
                placed = sm.pop("spill_audit_placed")
                v_valid = sm.pop("spill_victim_valid")
                v_rec = sm.pop("spill_victim_rec")
                v_begin = sm.pop("spill_victim_begin")
                v_end = sm.pop("spill_victim_end")
                offered = ev["evict_valid"]
                sp_state = jnp.where(
                    placed, AUDIT_SPILLED,
                    jnp.where(offered, AUDIT_SPILL_DROPPED, 0))
                vic_state = jnp.where(v_valid, AUDIT_SPILL_OVERWROTE, 0)
                m["audit_rec"] = jnp.concatenate(
                    [m["audit_rec"], ev["evict_rec"], v_rec])
                m["audit_begin"] = jnp.concatenate(
                    [m["audit_begin"], ev["evict_begin"], v_begin])
                m["audit_end"] = jnp.concatenate(
                    [m["audit_end"], ev["evict_end"], v_end])
                m["audit_state"] = jnp.concatenate(
                    [m["audit_state"], sp_state.astype(jnp.int32),
                     vic_state.astype(jnp.int32)])
            m.update(sm)
    return ring_o, spill_s, m


def commit_sharded(store: ShardedVersionStore, w_rec: jax.Array,
                   w_key: jax.Array, w_valid: jax.Array,
                   w_begin_ts: jax.Array, w_end_ts: jax.Array,
                   w_data: jax.Array, watermark: jax.Array,
                   mesh=None, axis: str = "cc",
                   ts_window: Optional[Tuple[jax.Array, jax.Array]] = None,
                   pin_ts: Optional[jax.Array] = None,
                   with_audit: bool = False
                   ) -> Tuple[ShardedVersionStore, Dict[str, jax.Array]]:
    """Commit ALL batch versions into the partitioned rings (and live
    evictees into the spill pools).

    Inputs are the merged plan's global placeholder arrays (identical on
    every shard); each shard commits only the records it owns. Metrics are
    aggregated to match the single-ring ``commit_versions`` contract,
    except ``ring_overwrote_rec`` / ``ring_overwrote_dead_rec`` which stay
    per-shard [n, Rl] (use ``to_global`` for the [R] view). ``ts_window``
    (the epoch's global timestamp span — see ``commit_versions``) and
    ``pin_ts`` (registered snapshot pins, INF_TS-padded) are global
    scalars/vectors, so they replicate to every shard unchanged.

    ``with_audit=True`` adds the lifecycle audit arrays
    (``audit_rec/begin/end/state`` flattened over shards, record ids
    GLOBAL, rec = -1 where the state is 0/masked) and the
    ``ring_committed`` scalar — all lazy device values; nothing here
    synchronises.

    Every substrate — one shard, logical shards on one device, and each
    device's shard under ``shard_map`` — runs the per-shard body under
    ``vmap`` over the stacked leading axis, with no slice and restack of
    the store: a caller that donates the store (``BohmEngine``'s commit
    program) gets every leaf updated in place. One shard reports the
    single ring's metrics as they are.

    ``shard_writes`` counts the batch versions the shards commit (each
    shard's owned writes) and ``shard_writes_max`` the busiest shard's;
    one shard reports the two equal.
    """
    n = store.n_shards
    with_spill = store.spill is not None
    paged = store.paged

    def one_shard(prim_s, spill_s, k_eff_s, shard):
        with jax.named_scope("commit/ring"):
            rec_l, key_l, owned = _mask_to_shard(n, shard, w_rec, w_key,
                                                 w_valid)
            writes = jnp.sum(owned, dtype=jnp.int32)
        prim_o, spill_o, m = _commit_one_shard(
            prim_s, spill_s, k_eff_s, rec_l, key_l, owned, w_begin_ts,
            w_end_ts, w_data, watermark, ts_window, pin_ts,
            with_audit=with_audit)
        m["shard_writes"] = writes
        return prim_o, spill_o, m

    if mesh is not None and axis in mesh.shape and mesh.shape[axis] == n:
        from jax.sharding import PartitionSpec as P

        def body(prim, spill, k_eff):
            return jax.vmap(one_shard, in_axes=(0, 0, 0, None))(
                prim, spill, k_eff, jax.lax.axis_index(axis))

        out_struct = (_page_struct() if paged else _ring_struct(),
                      None if not with_spill else _spill_struct(),
                      _metrics_struct(with_spill, paged, with_audit))
        prim, spill, per = jax.shard_map(
            body, mesh=mesh, check_vma=False,
            in_specs=jax.tree.map(lambda _: P(axis),
                                  (_primary(store), store.spill,
                                   store.k_eff)),
            out_specs=jax.tree.map(lambda _: P(axis), out_struct))(
            _primary(store), store.spill, store.k_eff)
    else:
        prim, spill, per = jax.vmap(one_shard)(
            _primary(store), store.spill, store.k_eff,
            jnp.arange(n, dtype=jnp.int32))

    new_store = dataclasses.replace(_with_primary(store, prim), spill=spill)
    if n == 1:
        # one shard reports the single ring's metrics as they are
        metrics = {k: v if k in ("ring_overwrote_rec",
                                 "ring_overwrote_dead_rec") else v[0]
                   for k, v in per.items()}
        metrics["shard_writes_max"] = metrics["shard_writes"]
        if with_audit:
            metrics["audit_rec"] = jnp.where(
                metrics["audit_state"] > 0, metrics["audit_rec"], -1)
        return new_store, metrics

    R = store.num_records
    metrics = {
        "ring_evicted": jnp.sum(per["ring_evicted"]),
        "ring_overflow_dropped": jnp.sum(per["ring_overflow_dropped"]),
        "ring_overwrote_live": jnp.sum(per["ring_overwrote_live"]),
        "ring_overwrote_dead": jnp.sum(per["ring_overwrote_dead"]),
        "ring_overwrote_rec": per["ring_overwrote_rec"],        # [n, Rl]
        "ring_overwrote_dead_rec": per["ring_overwrote_dead_rec"],
        "ring_occ_max": jnp.max(per["ring_occ_max"]),
        # per-shard means weight hash-padding records with 0 occupancy;
        # renormalise to the real record count
        "ring_occ_mean": jnp.sum(per["ring_occ_mean"])
        * store.records_per_shard / R,
        # the partition's load: versions committed, and the busiest
        # shard's share of them (hash-partitioned zipf keys load the
        # shards unevenly)
        "shard_writes": jnp.sum(per["shard_writes"]),
        "shard_writes_max": jnp.max(per["shard_writes"]),
    }
    if paged:
        for k in ("paged_alloc_failed", "paged_pages_allocated",
                  "paged_pages_free"):
            metrics[k] = jnp.sum(per[k])
    if with_spill:
        for k in ("spill_freed", "spill_admitted", "spill_dropped",
                  "spill_overwrote", "spill_overwrote_pinned",
                  "spill_occupancy"):
            metrics[k] = jnp.sum(per[k])
    if with_audit:
        metrics["ring_committed"] = jnp.sum(per["ring_committed"])
        # shard-local audit record ids -> global (r = local * n + shard),
        # flattened over the shard axis; masked entries stay rec = -1
        shard_ix = jnp.arange(n, dtype=jnp.int32)[:, None]
        state = per["audit_state"]
        metrics["audit_rec"] = jnp.where(
            state > 0, per["audit_rec"] * n + shard_ix, -1).reshape(-1)
        metrics["audit_begin"] = per["audit_begin"].reshape(-1)
        metrics["audit_end"] = per["audit_end"].reshape(-1)
        metrics["audit_state"] = state.reshape(-1)
    return new_store, metrics


def _ring_struct():
    z = jnp.zeros((), jnp.int32)
    return VersionRing(begin=z, end=z, payload=z, head=z)


def _page_struct():
    z = jnp.zeros((), jnp.int32)
    return PageSlab(begin=z, end=z, payload=z, page_table=z, head=z)


def _spill_struct():
    z = jnp.zeros((), jnp.int32)
    return SpillPool(begin=z, end=z, rec=z, payload=z)


def _metrics_struct(with_spill: bool = False, paged: bool = False,
                    with_audit: bool = False):
    z = jnp.zeros((), jnp.int32)
    m = {"ring_evicted": z, "ring_overflow_dropped": z,
         "ring_overwrote_live": z, "ring_overwrote_dead": z,
         "ring_overwrote_rec": z, "ring_overwrote_dead_rec": z,
         "ring_occ_max": z, "ring_occ_mean": z, "shard_writes": z}
    if paged:
        m.update({"paged_alloc_failed": z, "paged_pages_allocated": z,
                  "paged_pages_free": z})
    if with_spill:
        m.update({"spill_freed": z, "spill_admitted": z,
                  "spill_dropped": z, "spill_overwrote": z,
                  "spill_overwrote_pinned": z, "spill_occupancy": z})
    if with_audit:
        m.update({"ring_committed": z, "audit_rec": z, "audit_begin": z,
                  "audit_end": z, "audit_state": z})
    return m


def gc_sharded(store: ShardedVersionStore, watermark: jax.Array
               ) -> Tuple[ShardedVersionStore, jax.Array]:
    """Standalone watermark GC sweep over every shard (see ``gc_ring`` /
    ``gc_spill`` / ``gc_pages``).  The dense condition ``end <=
    watermark`` is per-slot elementwise with a global scalar watermark,
    so it runs unchanged over the stacked [n, Rl, K] (and [n, B, S])
    arrays on ANY substrate — mesh-sharded device arrays, vmapped
    logical shards, or the single ring. The paged sweep additionally
    returns fully-drained stranded pages to each shard's free list
    (per-shard scatters, vmapped over the shard axis)."""
    if store.rings is not None:
        prim, evicted = gc_ring(store.rings, watermark)
    else:
        prim, per_shard = jax.vmap(
            lambda p, k: gc_pages(p, watermark, k)
        )(store.pages, store.k_eff)
        evicted = jnp.sum(per_shard)
    spill = store.spill
    if spill is not None:
        spill, freed = gc_spill(spill, watermark)
        evicted = evicted + freed
    return dataclasses.replace(_with_primary(store, prim),
                               spill=spill), evicted


def _audit_dead_flat(store: ShardedVersionStore, watermark: jax.Array
                     ) -> Tuple[jax.Array, jax.Array, jax.Array, jax.Array]:
    """Flatten every version the sweep at ``watermark`` is about to
    reclaim — primary (dense or paged) plus spill — into parallel
    (rec_global, begin, end, dead) arrays. Record ids are global
    (``-1`` where not reclaimed / unowned)."""
    n, Rl = store.n_shards, store.records_per_shard
    wm = jnp.asarray(watermark, jnp.int32)
    parts = []
    if store.rings is not None:
        r = store.rings
        dead = (r.begin != INF_TS) & (r.end <= wm)         # [n, Rl, K]
        rec_g = jnp.broadcast_to(
            global_record_ids(n, Rl)[..., None], dead.shape)
        parts.append((rec_g, r.begin, r.end, dead))
    else:
        p = store.pages
        dead = (p.begin != INF_TS) & (p.end <= wm)         # [n, P, S]
        owner = jax.vmap(
            lambda pt: page_owner_index(pt, p.num_pages)[0])(p.page_table)
        shard = jnp.arange(n, dtype=jnp.int32)[:, None]
        rec_g = jnp.where(owner >= 0, owner * n + shard, -1)   # [n, P]
        rec_g = jnp.broadcast_to(rec_g[..., None], dead.shape)
        parts.append((rec_g, p.begin, p.end, dead & (rec_g >= 0)))
    if store.spill is not None:
        sp = store.spill
        dead = (sp.rec >= 0) & (sp.end <= wm)              # [n, B, S]
        shard = jnp.arange(n, dtype=jnp.int32)[:, None, None]
        rec_g = jnp.where(sp.rec >= 0, sp.rec * n + shard, -1)
        parts.append((rec_g, sp.begin, sp.end, dead))
    rec = jnp.concatenate(
        [jnp.where(d, r, -1).reshape(-1) for r, _, _, d in parts])
    begin = jnp.concatenate([b.reshape(-1) for _, b, _, _ in parts])
    end = jnp.concatenate([e.reshape(-1) for _, _, e, _ in parts])
    dead = jnp.concatenate([d.reshape(-1) for _, _, _, d in parts])
    return rec, begin, end, dead


def gc_sharded_audited(store: ShardedVersionStore, watermark: jax.Array,
                       pin_ts: Optional[jax.Array] = None,
                       event_cap: int = 256
                       ) -> Tuple[ShardedVersionStore, jax.Array,
                                  Dict[str, jax.Array]]:
    """``gc_sharded`` plus the GC audit: how long after death each
    reclaimed version was actually swept (the Ben-David et al.
    death->reclamation delay) and whether any registered pin could still
    have stabbed it (must be impossible — ``watermark <= min(pin_ts)``
    by construction; the audit *certifies* rather than assumes it).

    Returns ``(store, evicted, audit)`` where ``audit`` holds LAZY
    device values only (the auditor harvests them at boundaries):

      gc_watermark      []    the sweep's watermark
      gc_dead_total     []    versions reclaimed by this sweep
      gc_delay_sum/max  []    sum / max of (watermark - end) over them
      gc_delay_hist     [16]  log2-bucketed delay histogram
      gc_pin_stabbed    []    reclaimed versions a pin stabs (cert == 0)
      gc_event_rec/begin/end [event_cap]  the first ``event_cap``
                        reclaimed versions (global rec, -1/INF padded)
    """
    wm = jnp.asarray(watermark, jnp.int32)
    rec, begin, end, dead = _audit_dead_flat(store, wm)
    delay = jnp.where(dead, wm - end, 0)
    bucket = jnp.clip(
        jnp.floor(jnp.log2(delay.astype(jnp.float32) + 1.0)),
        0, 15).astype(jnp.int32)
    hist = jnp.zeros((16,), jnp.int32).at[
        jnp.where(dead, bucket, 16)].add(1, mode="drop")
    stabbed = dead & pin_stabbed(begin, end, pin_ts)
    n_flat = dead.shape[0]
    idx = jnp.nonzero(dead, size=int(event_cap), fill_value=n_flat)[0]

    def take(x, fill):
        return jnp.concatenate(
            [x, jnp.full((1,), fill, x.dtype)])[jnp.minimum(idx, n_flat)]

    audit = {
        "gc_watermark": wm,
        "gc_dead_total": jnp.sum(dead),
        "gc_delay_sum": jnp.sum(delay),
        "gc_delay_max": jnp.max(delay),
        "gc_delay_hist": hist,
        "gc_pin_stabbed": jnp.sum(stabbed),
        "gc_event_rec": take(rec, -1),
        "gc_event_begin": take(begin, INF_TS),
        "gc_event_end": take(end, INF_TS),
    }
    new_store, evicted = gc_sharded(store, wm)
    return new_store, evicted, audit


# ---------------------------------------------------------------------------
# Snapshot reads: per-shard gather + mvcc_resolve (primary, then the spill
# fall-through), merged by ownership.
# ---------------------------------------------------------------------------
def gather_windows_sharded(store: ShardedVersionStore, records: jax.Array
                           ) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """(begin [B, K], end [B, K], payload [B, K, D]) candidate windows per
    read, gathered from each record's owning shard (primary level only —
    the spill fall-through lives in ``resolve_sharded``). For a paged
    store the windows are materialised through the page table (K =
    MaxP * S, unmapped pages contribute empty slots)."""
    if store.n_shards == 1:
        prim = _ring0(store)
        if isinstance(prim, PageSlab):
            return gather_windows_paged(prim, records)
        return gather_windows(prim, records)
    n = store.n_shards
    rec = jnp.maximum(jnp.asarray(records, jnp.int32), 0)
    shard, loc = rec % n, rec // n
    if store.paged:
        p = store.pages
        pt = p.page_table[shard, loc]                     # [B, MaxP]
        safe = jnp.maximum(pt, 0)
        sh = shard[:, None]
        return mask_gathered_windows(pt, p.begin[sh, safe],
                                     p.end[sh, safe],
                                     p.payload[sh, safe])
    r = store.rings
    return r.begin[shard, loc], r.end[shard, loc], r.payload[shard, loc]


def _resolve_two_level(prim_s, spill_s: Optional[SpillPool],
                       local_rec: jax.Array, ts: jax.Array
                       ) -> Tuple[jax.Array, jax.Array]:
    """Primary resolve with the spill fall-through: at most one of the
    two levels holds the version visible at ``ts`` (a version is evicted
    from the primary exactly when it moves to spill, and [begin, end)
    windows partition a record's timeline), so combining is a select.
    The primary's candidate windows come from the dense ring or, for a
    page slab, through the page table (unmapped pages contribute empty
    slots); both resolve through the same ``mvcc_resolve`` kernel."""
    gather = gather_windows_paged if isinstance(prim_s, PageSlab) \
        else gather_windows
    with jax.named_scope("resolve/gather"):
        windows = gather(prim_s, local_rec)
    vals, found = ops.mvcc_resolve(*windows, ts)
    if spill_s is None:
        return vals, found
    with jax.named_scope("resolve/gather"):
        bkt = spill_buckets_for(local_rec, spill_s.begin.shape[0])
        s_windows = (spill_s.begin[bkt], spill_s.end[bkt],
                     spill_s.rec[bkt], local_rec, spill_s.payload[bkt])
    s_vals, s_found = ops.mvcc_resolve_masked(*s_windows, ts)
    return jnp.where(found[:, None], vals, s_vals), found | s_found


def resolve_sharded(store: ShardedVersionStore, records: jax.Array,
                    ts: jax.Array, mesh=None, axis: str = "cc"
                    ) -> Tuple[jax.Array, jax.Array]:
    """Resolve ``records`` [B] at snapshot timestamps ``ts`` [B] through
    the Pallas kernel, PER SHARD: each shard runs ``mvcc_resolve`` over
    the reads it owns against its local ring, falling through to its
    spill pool for versions the primary ring evicted; per-read results
    merge by ownership (foreign shards contribute zeros / found=False).
    Returns (vals [B, D], found [B])."""
    n = store.n_shards
    records = jnp.asarray(records, jnp.int32)
    if n == 1:
        local = jnp.maximum(records, 0)
        with jax.named_scope("resolve/gather"):
            prim0, spill0 = _ring0(store), _take_spill(store, 0)
        return _resolve_two_level(prim0, spill0, local, ts)

    def one_shard(prim_s, spill_s, shard):
        with jax.named_scope("resolve/gather"):
            owned = (records % n) == shard
            local = jnp.where(owned, records // n, 0)
        vals, found = _resolve_two_level(prim_s, spill_s, local, ts)
        return jnp.where(owned[:, None], vals, 0), owned & found

    if mesh is not None and axis in mesh.shape and mesh.shape[axis] == n:
        from jax.sharding import PartitionSpec as P

        def body(prim, spill):
            squeeze = lambda t: jax.tree.map(lambda x: x[0], t)  # noqa: E731
            with jax.named_scope("resolve/gather"):
                prim_s = squeeze(prim)
                spill_s = None if spill is None else squeeze(spill)
            vals, found = one_shard(prim_s, spill_s,
                                    jax.lax.axis_index(axis))
            # each read is owned by exactly one shard: sum == select
            return (jax.lax.psum(vals, axis),
                    jax.lax.psum(found.astype(jnp.int32), axis) > 0)

        return jax.shard_map(
            body, mesh=mesh, check_vma=False,
            in_specs=jax.tree.map(lambda _: P(axis),
                                  (_primary(store), store.spill)),
            out_specs=(P(), P()))(_primary(store), store.spill)

    # logical shards on one device: unrolled kernel calls (n is static),
    # merged by ownership — XLA schedules the independent shard resolves
    # side by side.
    vals = None
    found = None
    for s in range(n):
        with jax.named_scope("resolve/gather"):
            prim_s, spill_s = _take_shard(store, s), _take_spill(store, s)
        v_s, f_s = one_shard(prim_s, spill_s, jnp.int32(s))
        vals = v_s if vals is None else vals + v_s
        found = f_s if found is None else found | f_s
    return vals, found
