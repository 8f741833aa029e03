"""Paged physical version storage (repro.store.pages):

  1. the fused page-table resolve kernel matches its jnp reference (and
     degrades to the dense kernel on a fully-mapped table);
  2. the headline property: a paged BohmEngine is BYTE-IDENTICAL to the
     dense-ring engine — per-batch read values, head store, base_ts,
     ts_counter, pinned snapshot reads before and after ``gc_sweep``,
     spill pool bytes and the live-eviction histogram — at 1 and 2
     logical shards, fixed-K and page-quantized adaptive-K, and on a
     4-device mesh (subprocess);
  3. the conflict-aware ``TxnService`` (merged epochs, deferred commits,
     plan-time pins) over a paged+spill store stays byte-identical to
     sequential dense ``run_batch``;
  4. page lifecycle: cold records hold one page, hot records are granted
     pages from the free list, and after the hot set cools (EWMA
     pressure decay) + pins release, ``gc_sweep`` reclaims the stranded
     pages back to the free list;
  5. a deliberately tiny slab exhausts its free list: writes are dropped
     and counted (``paged_alloc_failed``), and reads then report
     found=False — never a stale payload;
  6. policy: the page-quantized ``reassign_k`` keeps all invariants in
     quantum units; ``decay_pressure`` halves per half-life.
"""
import os
import subprocess
import sys
import textwrap
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.engine import BohmEngine
from repro.core.txn import Workload, make_batch
from repro.core.workloads import gen_ycsb_batch, make_ycsb
from repro.kernels import ops, ref
from repro.service import TxnService
from repro.store import decay_pressure, reassign_k
from repro.store.pages import PageSlab, gather_windows_paged

R, T = 64, 32


def _zipf_batch(rng, theta=0.9, ops_n=4):
    return gen_ycsb_batch(rng, T, R, theta=theta, mix="10rmw", ops=ops_n)


def _hot_workload():
    def bump(vals, args):
        return vals.at[..., 0].add(1), jnp.zeros((), bool)

    return Workload(name="hot", n_read=1, n_write=1, payload_words=1,
                    branches=(bump,))


def _rec_batch(recs, n_txns=8):
    """n_txns single-record updates round-robining over ``recs``."""
    col = np.asarray([recs[i % len(recs)] for i in range(n_txns)])[:, None]
    return make_batch(col, col.copy(), np.zeros(n_txns),
                      np.zeros((n_txns, 1)))


def _assert_engines_equal(dense, paged, snaps, psnaps):
    """The byte-identity bundle: head store, ts_counter, pinned reads,
    spill bytes, pressure histograms."""
    np.testing.assert_array_equal(np.asarray(dense.store.base),
                                  np.asarray(paged.store.base))
    np.testing.assert_array_equal(np.asarray(dense.store.base_ts),
                                  np.asarray(paged.store.base_ts))
    assert int(dense.store.ts_counter) == int(paged.store.ts_counter)
    for s, p in zip(snaps, psnaps):
        assert s.ts == p.ts
        v_d, f_d = dense.snapshot_read(np.arange(R), s)
        v_p, f_p = paged.snapshot_read(np.arange(R), p)
        np.testing.assert_array_equal(np.asarray(f_d), np.asarray(f_p))
        np.testing.assert_array_equal(np.asarray(v_d), np.asarray(v_p))
    if dense.store.versions.spill is not None:
        for a, b in zip(jax.tree.leaves(dense.store.versions.spill),
                        jax.tree.leaves(paged.store.versions.spill)):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    np.testing.assert_array_equal(np.asarray(dense.overflow_by_record()),
                                  np.asarray(paged.overflow_by_record()))


# ---------------------------------------------------------------------------
# 1. the paged resolve path (page-table gather -> mvcc_resolve) == jnp
#    reference
# ---------------------------------------------------------------------------
def _paged_resolve(pt, begin, end, data, ts):
    """Read i resolves record i of a slab whose page table is ``pt`` —
    the store's paged read path: ``gather_windows_paged`` then the
    ``mvcc_resolve`` kernel."""
    slab = PageSlab(begin=jnp.asarray(begin), end=jnp.asarray(end),
                    payload=jnp.asarray(data), page_table=jnp.asarray(pt),
                    head=jnp.zeros((pt.shape[0],), jnp.int32))
    windows = gather_windows_paged(slab, jnp.arange(pt.shape[0]))
    return ops.mvcc_resolve(*windows, jnp.asarray(ts))


def test_paged_resolve_kernel_matches_ref():
    rng = np.random.default_rng(3)
    P, S, MaxP, B, D = 23, 3, 4, 37, 5
    # a consistent store never repeats a page in one row (a page has one
    # owner) nor a begin ts within a record — generate accordingly
    begin = rng.permutation(P * S * 2)[:P * S].reshape(P, S).astype(
        np.int32)
    end = begin + rng.integers(1, 30, (P, S)).astype(np.int32)
    data = rng.integers(0, 99, (P, S, D)).astype(np.int32)
    pt = np.stack([rng.permutation(P)[:MaxP] for _ in range(B)]).astype(
        np.int32)
    pt[rng.random((B, MaxP)) < 0.4] = -1             # unmap some entries
    ts = rng.integers(0, 80, B).astype(np.int32)
    v_k, f_k = _paged_resolve(pt, begin, end, data, ts)
    v_r, f_r = ref.mvcc_resolve_paged_ref(pt, jnp.asarray(begin),
                                          jnp.asarray(end),
                                          jnp.asarray(data),
                                          jnp.asarray(ts))
    np.testing.assert_array_equal(np.asarray(v_k), np.asarray(v_r))
    np.testing.assert_array_equal(np.asarray(f_k), np.asarray(f_r))
    # an all-unmapped row finds nothing
    assert not np.asarray(f_k)[np.all(pt < 0, axis=1)].any()
    # a fully-mapped single-page table degrades to the dense kernel over
    # that page's window
    pt1 = np.arange(B, dtype=np.int32)[:, None] % P
    v_m, f_m = _paged_resolve(pt1, begin, end, data, ts)
    v_p, f_p = ops.mvcc_resolve(begin[pt1[:, 0]], end[pt1[:, 0]],
                                data[pt1[:, 0]], ts)
    np.testing.assert_array_equal(np.asarray(v_m), np.asarray(v_p))
    np.testing.assert_array_equal(np.asarray(f_m), np.asarray(f_p))


# ---------------------------------------------------------------------------
# 2. paged engine == dense engine, byte for byte
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("n_shards", [1, 2])
@pytest.mark.parametrize("adaptive", [False, True])
def test_paged_matches_dense_engine(n_shards, adaptive):
    """Zipfian update stream with rolling pins and mid-stream sweeps:
    the paged store must answer every read byte-identically to the dense
    ring store. With ``adaptive`` both engines run the page-quantized
    policy (the dense twin via ``k_quantum``), so k_eff trajectories —
    and therefore overflow, spill and read behaviour — coincide."""
    wl = make_ycsb(payload_words=2, ops=4)
    kw = dict(ring_slots=4, n_shards=n_shards, spill_buckets=16,
              spill_slots=16)
    if adaptive:
        kw.update(adaptive_k=True, k_max=8)
    dense = BohmEngine(R, wl, k_quantum=2 if adaptive else None, **kw)
    paged = BohmEngine(R, wl, paged=True, page_slots=2,
                       pages_per_shard=256, **kw)
    rng = np.random.default_rng(11)

    snaps, psnaps = [], []
    for i in range(8):
        batch = _zipf_batch(rng, theta=1.1)
        r_d, m_d = dense.run_batch(batch)
        r_p, m_p = paged.run_batch(batch)
        np.testing.assert_array_equal(np.asarray(r_d), np.asarray(r_p))
        assert int(m_d["ring_overwrote_live"]) == int(
            m_p["ring_overwrote_live"])
        if i % 2 == 1:
            snaps.append(dense.begin_snapshot())
            psnaps.append(paged.begin_snapshot())
            while len(snaps) > 2:
                dense.release_snapshot(snaps.pop(0))
                paged.release_snapshot(psnaps.pop(0))
            dense.gc_sweep()
            paged.gc_sweep()
            np.testing.assert_array_equal(np.asarray(dense.k_by_record()),
                                          np.asarray(paged.k_by_record()))

    assert int(jnp.sum(paged.overflow_by_record())) > 0   # stream overflows
    assert paged.storage_stats()["alloc_failed"] == 0     # sized adequately
    _assert_engines_equal(dense, paged, snaps, psnaps)
    # a second sweep on both sides is a no-op and identity still holds
    dense.gc_sweep()
    paged.gc_sweep()
    _assert_engines_equal(dense, paged, snaps, psnaps)


# ---------------------------------------------------------------------------
# 3. the conflict-aware scheduler over a paged + spill store
# ---------------------------------------------------------------------------
def test_paged_service_conflict_aware_matches_sequential_dense():
    """TxnService with merged epochs / deferred commits / plan-time pins
    over the PAGED store == sequential dense run_batch, byte for byte
    (per-ticket reads, pinned snapshot reads, head store)."""
    wl = make_ycsb(payload_words=2, ops=4)
    rng = np.random.default_rng(31)
    batches = [_zipf_batch(rng) for _ in range(6)]

    e0 = BohmEngine(R, wl, ring_slots=2, spill_buckets=16, spill_slots=16)
    seq_reads, seq_snaps = [], []
    for i, b in enumerate(batches):
        r, _ = e0.run_batch(b)
        seq_reads.append(np.asarray(r))
        if i % 2 == 1:
            seq_snaps.append(e0.begin_snapshot())

    e1 = BohmEngine(R, wl, ring_slots=2, spill_buckets=16, spill_slots=16,
                    paged=True, page_slots=2, pages_per_shard=256)
    svc = TxnService(e1, max_inflight=2, admission_window=2)
    svc_snaps, tickets = [], []
    for i, b in enumerate(batches):
        tickets.append(svc.submit(b))
        if i % 2 == 1:
            svc_snaps.append(svc.begin_snapshot())
    for t, want in zip(tickets, seq_reads):
        got = svc.wait(t)
        np.testing.assert_array_equal(np.asarray(got.read_vals), want)
    svc.drain()

    np.testing.assert_array_equal(np.asarray(e0.store.base),
                                  np.asarray(e1.store.base))
    for s0, s1 in zip(seq_snaps, svc_snaps):
        assert s0.ts == s1.ts
        v0, f0 = e0.snapshot_read(np.arange(R), s0)
        v1, f1 = e1.snapshot_read(np.arange(R), s1)
        np.testing.assert_array_equal(np.asarray(f0), np.asarray(f1))
        np.testing.assert_array_equal(np.asarray(v0), np.asarray(v1))
    e0.gc_sweep()
    e1.gc_sweep()
    for s0, s1 in zip(seq_snaps, svc_snaps):
        v0, f0 = e0.snapshot_read(np.arange(R), s0)
        v1, f1 = e1.snapshot_read(np.arange(R), s1)
        np.testing.assert_array_equal(np.asarray(f0), np.asarray(f1))
        np.testing.assert_array_equal(np.asarray(v0), np.asarray(v1))
    np.testing.assert_array_equal(np.asarray(e0.overflow_by_record()),
                                  np.asarray(e1.overflow_by_record()))


# ---------------------------------------------------------------------------
# 4. page lifecycle: grant on growth, reclaim after the hot set cools
# ---------------------------------------------------------------------------
def test_page_grant_and_reclaim_on_hotset_migration():
    """Hot record 0 is granted pages (adaptive grow beyond its initial
    page); when the hot set migrates to record 1 and the EWMA pressure
    on 0 decays to zero, the policy shrinks 0 back, its stranded pages
    drain at the watermark, and gc_sweep returns them to the free list —
    where record 1's growth picks them up."""
    wl = _hot_workload()
    # tight budget (4 records x 4 slots) against a tall k_max: the NEW
    # hot set can only reach its target by taking the OLD hot set's
    # pages, so release-on-cool is load-bearing, not cosmetic
    eng = BohmEngine(4, wl, ring_slots=4, adaptive_k=True, k_max=12,
                     paged=True, page_slots=2, pages_per_shard=12,
                     pressure_decay=1.0, spill_buckets=4, spill_slots=8)
    assert eng.storage_stats()["pages_mapped"] == 4   # one page each

    def pump(rec, n):
        for _ in range(n):
            pin = eng.begin_snapshot()
            eng.run_batch(_rec_batch([rec]))
            eng.gc_sweep()
            eng.release_snapshot(pin)

    def rec_pages(r):
        pt = np.asarray(eng.store.versions.pages.page_table)[0]
        return int((pt[r] >= 0).sum())

    pump(0, 4)
    k = np.asarray(eng.k_by_record())
    assert k[0] > 4 and k[0] % 2 == 0                 # page-granular grow
    r0_grown = rec_pages(0)
    assert r0_grown > 1                               # pages granted to 0

    # hot set migrates; record 0 cools — its EWMA pressure halves every
    # sweep and truncates to zero, it becomes a donor, and its drained
    # pages return to the free list to fund record 1
    pump(1, 10)
    k = np.asarray(eng.k_by_record())
    assert k[1] > 4 and k[1] % 2 == 0                 # new hot set grew
    assert k[0] <= 4                                  # old one released
    assert np.asarray(eng.k_by_record()).sum() == 4 * 4   # budget fixed
    assert rec_pages(0) < r0_grown                    # strands reclaimed
    assert rec_pages(1) > 1                           # ...and re-granted
    stats = eng.storage_stats()
    assert stats["pages_free"] > 0
    assert stats["alloc_failed"] == 0


def test_cumulative_pressure_holds_peak_grant_forever():
    """The counterfactual for the EWMA satellite: WITHOUT decay the old
    hot record's cumulative pressure never returns to zero, so it can
    never donate its grant back."""
    wl = _hot_workload()

    def run(decay):
        eng = BohmEngine(4, wl, ring_slots=4, adaptive_k=True, k_max=12,
                         paged=True, page_slots=2, pages_per_shard=12,
                         pressure_decay=decay, spill_buckets=4,
                         spill_slots=8)
        for rec, n in ((0, 4), (1, 10)):
            for _ in range(n):
                pin = eng.begin_snapshot()
                eng.run_batch(_rec_batch([rec]))
                eng.gc_sweep()
                eng.release_snapshot(pin)
        return np.asarray(eng.k_by_record())

    k_decay = run(1.0)
    k_hold = run(None)
    assert k_hold[0] > 4                  # cumulative: peak grant held
    assert k_decay[0] <= 4                # EWMA: released to the new set
    assert k_decay[1] > k_hold[1]         # and the new hot set got more


# ---------------------------------------------------------------------------
# 5. slab saturation: alloc failure drops, never a stale read
# ---------------------------------------------------------------------------
def test_paged_slab_saturation_never_stale():
    wl = make_ycsb(payload_words=2, ops=4)
    # 64 records, 64+2 pages of 1 slot: almost no growth headroom, and
    # k_eff=4 logical slots per record guarantee unsatisfiable requests
    eng = BohmEngine(R, wl, ring_slots=4, spill_slots=0, paged=True,
                     page_slots=1, pages_per_shard=R + 2)
    oracle = BohmEngine(R, wl, ring_slots=512, spill_slots=0)
    rng = np.random.default_rng(5)
    snaps, osnaps = [], []
    for _ in range(4):
        batch = _zipf_batch(rng, theta=1.1)
        eng.run_batch(batch)
        oracle.run_batch(batch)
        snaps.append(eng.begin_snapshot())
        osnaps.append(oracle.begin_snapshot())
    assert eng.storage_stats()["alloc_failed"] > 0    # it really saturated
    for s, o in zip(snaps, osnaps):
        v_e, f_e = eng.snapshot_read(np.arange(R), s)
        v_o, _ = oracle.snapshot_read(np.arange(R), o)
        f_e = np.asarray(f_e)
        np.testing.assert_array_equal(np.asarray(v_e)[f_e],
                                      np.asarray(v_o)[f_e])
        assert (np.asarray(v_e)[~f_e] == 0).all()


# ---------------------------------------------------------------------------
# 6. storage_stats: the memory story in numbers
# ---------------------------------------------------------------------------
def test_storage_stats_reports_footprint():
    wl = make_ycsb(payload_words=2, ops=4)
    paged = BohmEngine(256, wl, ring_slots=4, k_max=16, adaptive_k=True,
                       paged=True, page_slots=2, spill_slots=0)
    dense = BohmEngine(256, wl, ring_slots=4, k_max=16, adaptive_k=True,
                       spill_slots=0)
    sp, sd = paged.storage_stats(), dense.storage_stats()
    assert sp["layout"] == "paged" and sd["layout"] == "dense"
    # dense allocates R x k_max physically; the paged slab carries the
    # slot BUDGET (R x ring_slots) — 4x smaller here at equal k_max
    assert sd["physical_slots"] == 256 * 16
    assert sp["physical_slots"] == 256 * 4
    assert sp["physical_version_words"] < sd["physical_version_words"]
    # cold store: exactly one mapped page per record
    assert sp["pages_mapped"] == 256
    assert sp["mapped_slots"] == 256 * 2
    assert sp["slot_occupancy"] == sd["slot_occupancy"] == 256


# ---------------------------------------------------------------------------
# 7. policy units: quantum + decay
# ---------------------------------------------------------------------------
def test_reassign_k_quantum_unit():
    pressure = np.array([9, 0, 0, 0, 2, 0, 0, 0])
    k = np.full(8, 4)
    out = reassign_k(pressure, k, k_min=1, k_max=8, quantum=2)
    assert out.sum() == k.sum()                      # budget preserved
    assert (out % 2 == 0).all()                      # page-granular
    assert out.min() >= 1 and out.max() <= 8
    assert out[0] == 8                               # hottest fills first
    # fixpoint in quantum units
    np.testing.assert_array_equal(
        reassign_k(pressure, out, k_min=1, k_max=8, quantum=2), out)
    # occupancy floor honoured after rounding: a donor at occ=2 may not
    # shrink below ceil((2+1)/2)*2 = 4
    occ = np.array([0, 2, 0, 0, 0, 0, 0, 0])
    out2 = reassign_k(pressure, k, k_min=1, k_max=8, quantum=2,
                      occupancy=occ)
    assert out2[1] >= occ[1] + 1
    with pytest.raises(ValueError):
        reassign_k(pressure, np.full(8, 3), k_min=1, k_max=8, quantum=2)
    with pytest.raises(ValueError):
        reassign_k(pressure, k, k_min=1, k_max=7, quantum=2)


def test_decay_pressure_halves_per_half_life():
    p = decay_pressure(np.array([8.0]), np.array([0.0]), half_life=2.0)
    p = decay_pressure(p, np.array([0.0]), half_life=2.0)
    np.testing.assert_allclose(p, [4.0])
    # fresh deltas land at full weight
    p = decay_pressure(np.array([0.0]), np.array([5.0]), half_life=2.0)
    np.testing.assert_allclose(p, [5.0])
    with pytest.raises(ValueError):
        decay_pressure(np.array([1.0]), np.array([0.0]), half_life=0.0)


# ---------------------------------------------------------------------------
# 8. mesh substrate: the paged path through shard_map on 4 host devices
# (subprocess — repo convention), byte-equal to the dense mesh engine
# ---------------------------------------------------------------------------
_MESH_PAGED_SCRIPT = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
    import jax, jax.numpy as jnp
    import numpy as np
    from repro.core.engine import BohmEngine
    from repro.core.workloads import gen_ycsb_batch, make_ycsb

    R, T = 64, 32
    from repro.runtime import cc_mesh
    mesh = cc_mesh(4)
    wl = make_ycsb(payload_words=2, ops=4)
    e_paged = BohmEngine(R, wl, mesh=mesh, ring_slots=2, paged=True,
                         page_slots=2, pages_per_shard=64,
                         spill_buckets=16, spill_slots=16)
    e_dense = BohmEngine(R, wl, mesh=mesh, ring_slots=2,
                         spill_buckets=16, spill_slots=16)
    assert e_paged.n_shards == 4
    assert e_paged.store.versions.pages is not None
    rng = np.random.default_rng(13)
    snap_p = snap_d = None
    for i in range(5):
        batch = gen_ycsb_batch(rng, T, R, theta=0.9, ops=4)
        r_p, _ = e_paged.run_batch(batch)
        r_d, _ = e_dense.run_batch(batch)
        np.testing.assert_array_equal(np.asarray(r_p), np.asarray(r_d))
        if i == 0:
            snap_p = e_paged.begin_snapshot()
            snap_d = e_dense.begin_snapshot()
    assert int(jnp.sum(e_paged.overflow_by_record())) > 0
    v_p, f_p = e_paged.snapshot_read(np.arange(R), snap_p)
    v_d, f_d = e_dense.snapshot_read(np.arange(R), snap_d)
    np.testing.assert_array_equal(np.asarray(f_p), np.asarray(f_d))
    np.testing.assert_array_equal(np.asarray(v_p), np.asarray(v_d))
    assert bool(f_p.all())
    e_paged.gc_sweep()
    e_dense.gc_sweep()
    v_p2, f_p2 = e_paged.snapshot_read(np.arange(R), snap_p)
    np.testing.assert_array_equal(np.asarray(v_p2), np.asarray(v_p))
    np.testing.assert_array_equal(np.asarray(f_p2), np.asarray(f_p))
    print("MESH_PAGED_OK", e_paged.storage_stats()["pages_mapped"])
""")


def test_paged_mesh_substrate():
    root = Path(__file__).resolve().parents[1]
    env = dict(os.environ)
    env["PYTHONPATH"] = str(root / "src")
    env.pop("XLA_FLAGS", None)
    out = subprocess.run([sys.executable, "-c", _MESH_PAGED_SCRIPT],
                         capture_output=True, text=True, env=env,
                         cwd=str(root), timeout=600)
    assert out.returncode == 0, out.stderr[-2000:]
    assert "MESH_PAGED_OK" in out.stdout
