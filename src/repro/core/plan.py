"""Bohm concurrency-control phase (paper §4.1), TPU-native formulation.

The paper's CC threads insert placeholder versions record-by-record in
timestamp order and annotate reads with version references. The per-record
sequential insert becomes one sort + segment pass:

  1. every transaction t in the batch gets ts = ts_base + t (the paper's
     dedicated timestamp thread: a private counter, zero contention);
  2. flatten the write-sets to (record, ts) pairs and stable-sort by record
     — within a record, entries stay in ts order, which is exactly what one
     CC thread owning that record would have produced;
  3. a version's end_ts is its successor's begin_ts within the record
     segment (else infinity) — the paper's "update predecessor's end_ts";
  4. reads are resolved by binary search over the sorted (record, ts) keys:
     the visible version is the latest in-batch write with ts' < ts, else
     the base (pre-batch head) version. Read annotations are written into
     per-transaction plan rows — never into shared record state (the
     paper's "no writes to shared memory on reads" invariant).

Record-space partitioning (paper §4.1.2) shards this by record id with ZERO
communication: each shard sorts only the writes it owns (the batch is
replicated, each shard masks to its partition) — see ``cc_plan_sharded``.
"""
from __future__ import annotations

import dataclasses
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.txn import TxnBatch
from repro.store.ring import INF_TS  # single home of the ts sentinel

# composite (record, ts) uint32 keys need R * T < 2^32 (R <= 2^20 records,
# checked in the engine) — the one home of the batch/epoch size limit
MAX_BATCH_TXNS = 1 << 12


@jax.tree_util.register_dataclass
@dataclasses.dataclass(frozen=True)
class Plan:
    """Output of the CC phase — everything execution needs, precomputed."""
    # sorted placeholder versions (one per write-set entry, pads at end)
    w_rec: jax.Array        # [Nw] record id (INT32_MAX for pads)
    w_txn: jax.Array        # [Nw] local producer txn index
    w_end_local: jax.Array  # [Nw] local ts of invalidating txn (or T)
    w_valid: jax.Array      # [Nw] bool
    w_key: jax.Array        # [Nw] uint32 sorted (rec * T + t) keys
    # per-transaction annotations
    w_slot: jax.Array       # [T, W] slot of txn's writes in the sorted array
    r_dep_txn: jax.Array    # [T, Rd] local producer txn of each read (-1=base)
    r_dep_slot: jax.Array   # [T, Rd] version slot for each read (-1 = base)
    # commit info: batch-final versions become the new single-version heads
    commit_mask: jax.Array  # [Nw] bool: head version after the batch
    ts_base: jax.Array      # [] global timestamp of txn 0
    # global version lifetimes — consumed by the persistent version ring
    w_begin_ts: jax.Array   # [Nw] global begin ts (INF_TS for pads)
    w_end_ts: jax.Array     # [Nw] global end ts (INF_TS = open past batch)


def _keys(rec: jax.Array, t: jax.Array, T: int) -> jax.Array:
    """Composite (record, ts) ordering key in uint32. Requires R * T < 2^32
    (checked in the engine): R <= 2^20 records, T <= 2^12 batch."""
    return rec.astype(jnp.uint32) * jnp.uint32(T) + t.astype(jnp.uint32)


def cc_plan(batch: TxnBatch, ts_base: jax.Array) -> Plan:
    T, W = batch.write_set.shape
    Rd = batch.read_set.shape[1]
    Nw = T * W

    flat_rec = batch.write_set.reshape(-1)                    # [Nw]
    flat_t = jnp.repeat(jnp.arange(T, dtype=jnp.int32), W)    # [Nw]
    valid = flat_rec >= 0
    # pads sort to the end: key -> UINT32_MAX (avoid rec*T overflow)
    keys = jnp.where(valid, _keys(jnp.maximum(flat_rec, 0), flat_t, T),
                     jnp.uint32(0xFFFFFFFF))

    # stable: a txn whose write-set names the same record twice produces
    # duplicate (record, ts) keys — program order (write column) must break
    # the tie so the later write supersedes the earlier one.
    order = jnp.argsort(keys, stable=True)
    w_key = keys[order]
    w_rec = jnp.where(valid, flat_rec, jnp.int32(INF_TS))[order]
    w_txn = jnp.where(valid[order], flat_t[order], -1)
    w_valid = valid[order]

    # end timestamp: successor's begin within the same record segment
    nxt_rec = jnp.concatenate([w_rec[1:], jnp.full((1,), INF_TS, jnp.int32)])
    nxt_txn = jnp.concatenate([w_txn[1:], jnp.full((1,), T, jnp.int32)])
    same = nxt_rec == w_rec
    w_end_local = jnp.where(same, nxt_txn, T)                 # T == "infinity"
    commit_mask = w_valid & ~same                             # segment-last

    # inverse permutation: where did txn t's w-th write land?
    inv = jnp.zeros(Nw, jnp.int32).at[order].set(
        jnp.arange(Nw, dtype=jnp.int32))
    w_slot = jnp.where(valid.reshape(T, W), inv.reshape(T, W), -1)

    # read resolution: latest in-batch write with key strictly below the
    # reader's (record, ts) key — RMW reads its predecessor, not itself.
    r_rec = batch.read_set                                    # [T, Rd]
    r_t = jnp.broadcast_to(jnp.arange(T, dtype=jnp.int32)[:, None], (T, Rd))
    r_valid = r_rec >= 0
    r_keys = _keys(jnp.where(r_valid, r_rec, 0), r_t, T)
    pos = jnp.searchsorted(w_key, r_keys.reshape(-1), side="left") - 1
    pos = pos.reshape(T, Rd)
    cand_rec = jnp.where(pos >= 0, w_rec[jnp.maximum(pos, 0)], -1)
    hit = r_valid & (pos >= 0) & (cand_rec == r_rec)
    r_dep_slot = jnp.where(hit, pos, -1)
    r_dep_txn = jnp.where(hit, w_txn[jnp.maximum(pos, 0)], -1)

    ts_base = jnp.asarray(ts_base, jnp.int32)
    w_begin_ts = jnp.where(w_valid, ts_base + w_txn, INF_TS)
    w_end_ts = jnp.where(w_valid & (w_end_local < T),
                         ts_base + w_end_local, INF_TS)
    return Plan(w_rec=w_rec, w_txn=w_txn, w_end_local=w_end_local,
                w_valid=w_valid, w_key=w_key, w_slot=w_slot,
                r_dep_txn=r_dep_txn, r_dep_slot=r_dep_slot,
                commit_mask=commit_mask, ts_base=ts_base,
                w_begin_ts=w_begin_ts, w_end_ts=w_end_ts)


# ---------------------------------------------------------------------------
# Record-partitioned CC (paper §4.1.2) via shard_map: each shard receives the
# full batch (the paper: "every CC thread examines every transaction") and
# plans only the records it owns. No communication whatsoever inside the
# phase; the only synchronisation is the implicit batch barrier at the end.
# ---------------------------------------------------------------------------
def cc_plan_sharded(batch: TxnBatch, ts_base: jax.Array, mesh,
                    axis: str = "cc") -> Plan:
    n = mesh.shape[axis]

    def shard_fn(read_set, write_set, txn_type, args, ts_b):
        # the shard's own CC work, named on the device trace's ops
        with jax.named_scope("plan/shard"):
            shard = jax.lax.axis_index(axis)
            # mask write/read records not owned by this shard (hash
            # partition)
            owned_w = (write_set % n) == shard
            owned_r = (read_set % n) == shard
            local = TxnBatch(
                jnp.where(owned_r & (read_set >= 0), read_set, -1),
                jnp.where(owned_w & (write_set >= 0), write_set, -1),
                txn_type, args)
            p = cc_plan(local, ts_b)
            return jax.tree.map(lambda x: x[None], p)   # add shard axis

    from jax.sharding import PartitionSpec as P
    fn = jax.shard_map(
        shard_fn, mesh=mesh, check_vma=False,
        in_specs=(P(), P(), P(), P(), P()),
        out_specs=jax.tree.map(lambda _: P(axis), _plan_structure()))
    return fn(batch.read_set, batch.write_set, batch.txn_type, batch.args,
              jnp.asarray(ts_base, jnp.int32))


def _plan_structure():
    z = jnp.zeros((), jnp.int32)
    return Plan(w_rec=z, w_txn=z, w_end_local=z, w_valid=z, w_key=z,
                w_slot=z, r_dep_txn=z, r_dep_slot=z, commit_mask=z,
                ts_base=z, w_begin_ts=z, w_end_ts=z)


# ---------------------------------------------------------------------------
# Batch footprints: per-batch read/write record bitsets for the
# conflict-aware admission scheduler (``repro.service.TxnService``).
#
# Two adjacent batches commute — their merged CC epoch is provably
# identical to running them back-to-back — exactly when each batch's
# write-set is disjoint from the other's read UNION write set: no write of
# one can produce, invalidate, or be overwritten by anything the other
# touches, so the (record, ts) sort segments never interleave, every read
# resolves to the same producer, and the per-record ring arithmetic at
# commit is unchanged. The same condition lets exec(b+1) run against the
# pre-commit(b) store snapshot (exec reads only ``store.base`` rows in
# b+1's read-set, none of which commit(b) writes).
#
# Footprints live on the HOST (packed numpy uint64 bitsets): admission
# decisions are control flow, and a [R/64] word AND-reduce per candidate
# pair costs microseconds without touching the device queue.
#
# Signatures: every footprint also carries a single-uint64 BLOCK signature
# (bit j of the signature <=> some touched 64-record block w has
# w % 64 == j) — the length-bucketing idiom applied to record bitsets.
# Disjoint signatures are a *certificate* of disjoint footprints, so the
# out-of-order admission scheduler's window scan tests one word before
# falling back to the [R/64] word scan: disjoint-bucket pairs (different
# key stripes, a point batch vs a far scan) short-circuit, and the
# O(window^2) pairwise scan is near-O(window) on striped traffic. The fold
# is over BLOCK ids, not record ids, because any footprint wider than 64
# records saturates a record-residue fold into all-ones (no certificates);
# block residues keep stripes up to 4096 records on distinct bits.
# ---------------------------------------------------------------------------
def _fold_sig(bits: np.ndarray) -> int:
    """uint64 block signature of a packed bitset (see note above)."""
    nz = np.flatnonzero(bits)
    if not nz.size:
        return 0
    return int(np.bitwise_or.reduce(
        np.uint64(1) << (nz.astype(np.uint64) & np.uint64(63))))


@dataclasses.dataclass(frozen=True)
class BatchFootprint:
    """Packed per-batch record bitsets (bit r set <=> record r touched)
    plus their uint64 signatures (computed once at admission)."""
    read_bits: np.ndarray    # [ceil(R/64)] uint64, reads incl. RMW reads
    write_bits: np.ndarray   # [ceil(R/64)] uint64
    write_sig: int = -1      # block signature of write_bits (< 0: compute)
    rw_sig: int = -1         # block signature of read_bits | write_bits

    def __post_init__(self):
        if self.write_sig < 0:
            object.__setattr__(self, "write_sig",
                               _fold_sig(self.write_bits))
        if self.rw_sig < 0:
            object.__setattr__(self, "rw_sig",
                               _fold_sig(self.read_bits | self.write_bits))

    @property
    def rw_bits(self) -> np.ndarray:
        return self.read_bits | self.write_bits


def _pack_bits(records: np.ndarray, num_records: int) -> np.ndarray:
    bits = np.zeros((num_records + 63) // 64, np.uint64)
    rec = records[records >= 0].astype(np.int64).reshape(-1)
    np.bitwise_or.at(bits, rec >> 6, np.uint64(1) << (rec & 63).astype(
        np.uint64))
    return bits


def batch_footprint(batch: TxnBatch, num_records: int) -> BatchFootprint:
    """One pass over the batch's read/write sets at admission time."""
    return BatchFootprint(
        read_bits=_pack_bits(np.asarray(batch.read_set), num_records),
        write_bits=_pack_bits(np.asarray(batch.write_set), num_records))


def signatures_disjoint(a: BatchFootprint, b: BatchFootprint) -> bool:
    """One-word certificate: True guarantees ``not footprints_conflict``.

    False means "may conflict" — the caller falls back to the word scan.
    """
    return not ((a.write_sig & b.rw_sig) | (b.write_sig & a.rw_sig))


def footprints_conflict(a: BatchFootprint, b: BatchFootprint) -> bool:
    """True when the batches do NOT commute: some write of one intersects
    the other's read-or-write set (in either direction).

    The uint64 signature check runs first; only pairs whose signatures
    collide pay for the [R/64] word scan."""
    if signatures_disjoint(a, b):
        return False
    return bool(np.any(a.write_bits & b.rw_bits)
                or np.any(b.write_bits & a.rw_bits))


def conflict_witness(a: BatchFootprint, b: BatchFootprint
                     ) -> Optional[int]:
    """A concrete record id proving ``footprints_conflict(a, b)``: the
    lowest record written by one batch and touched (read or written) by
    the other. Returns None when the footprints commute.

    This is the flight recorder's conflict-attribution primitive: when
    the scheduler declines to merge/hop a batch, the witness names WHICH
    record blocked it — derived from the same packed bitsets the
    disjointness test already scanned, so attribution costs one extra
    word scan and only runs on the (rare) conflict path."""
    for cross in (a.write_bits & b.rw_bits, b.write_bits & a.rw_bits):
        nz = np.flatnonzero(cross)
        if nz.size:
            w = int(nz[0])
            bit = int(cross[w])
            return w * 64 + ((bit & -bit).bit_length() - 1)
    return None


def merge_footprints(a: BatchFootprint, b: BatchFootprint) -> BatchFootprint:
    # a block is touched in a|b iff it is touched in a or in b, so
    # merged signatures are the OR of the member signatures — free
    return BatchFootprint(read_bits=a.read_bits | b.read_bits,
                          write_bits=a.write_bits | b.write_bits,
                          write_sig=a.write_sig | b.write_sig,
                          rw_sig=a.rw_sig | b.rw_sig)


def merge_batches(a: TxnBatch, b: TxnBatch) -> TxnBatch:
    """Concatenate two batches into one CC epoch, preserving submission
    order (txn t of ``b`` becomes txn ``a.size + t``, so every global
    timestamp is identical to running the batches back-to-back). Callers
    must have checked ``not footprints_conflict(...)`` for the merged
    epoch to be equivalent; widths must agree (pad columns line up)."""
    if (a.n_read, a.n_write, a.args.shape[1:]) != \
            (b.n_read, b.n_write, b.args.shape[1:]):
        raise ValueError("merge_batches requires identical batch widths")
    return TxnBatch(
        read_set=jnp.concatenate([a.read_set, b.read_set]),
        write_set=jnp.concatenate([a.write_set, b.write_set]),
        txn_type=jnp.concatenate([a.txn_type, b.txn_type]),
        args=jnp.concatenate([a.args, b.args]))


def merge_sharded_plan(plan: Plan, batch: TxnBatch) -> Plan:
    """Collapse a [n_shard, ...] plan into the single-store layout.

    Per-shard slots index into per-shard version arrays; execution uses
    (shard, slot) pairs encoded as shard * Nw + slot. Reads/writes merge by
    maximum (each entry is owned by exactly one shard; others hold -1/pads).
    On a mesh this is the exchange between the shards' plans; its ops are
    named ``plan/merge``.
    """
    with jax.named_scope("plan/merge"):
        n = plan.w_rec.shape[0]
        Nw = plan.w_rec.shape[1]
        off = (jnp.arange(n, dtype=jnp.int32) * Nw)[:, None]

        def enc(slot2d):
            return jnp.where(slot2d >= 0, slot2d + off.reshape(
                (n,) + (1,) * (slot2d.ndim - 1)), -1)

        w_slot = jnp.max(enc(plan.w_slot), axis=0)
        r_dep_slot = jnp.max(enc(plan.r_dep_slot), axis=0)
        r_dep_txn = jnp.max(plan.r_dep_txn, axis=0)
        return Plan(
            w_rec=plan.w_rec.reshape(-1),
            w_txn=plan.w_txn.reshape(-1),
            w_end_local=plan.w_end_local.reshape(-1),
            w_valid=plan.w_valid.reshape(-1),
            w_key=plan.w_key.reshape(-1),
            w_slot=w_slot, r_dep_txn=r_dep_txn, r_dep_slot=r_dep_slot,
            commit_mask=plan.commit_mask.reshape(-1),
            ts_base=plan.ts_base.reshape(-1)[0],
            w_begin_ts=plan.w_begin_ts.reshape(-1),
            w_end_ts=plan.w_end_ts.reshape(-1))
