"""Hierarchical version storage (repro.store.spill + policy):

  1. live K-ring evictions land in the spill pool and historical reads
     fall through primary -> spill, byte-identical to an unbounded-K
     oracle ring (per-record reads at pinned snapshots, before and after
     ``gc_sweep``) at 1 and 2 logical shards and on a 4-device mesh
     (subprocess);
  2. the live/dead eviction split: versions superseded with no pin inside
     their window are DEAD — they never reach the spill pool or the
     policy histogram (the satellite fix: the old ``end > watermark``
     test counted them as live);
  3. ``gc_sweep`` is idempotent (two consecutive sweeps byte-identical)
     and drains the spill pool back to its initial state once every pin
     releases;
  4. adaptive K: the reassignment pass is budget-preserving,
     bound-respecting, deterministic and a fixpoint; the engine grows hot
     records at sweep boundaries and stays read-correct;
  5. the masked resolve kernel (the spill read path) matches its jnp
     reference, interpret-mode parity with the primary kernel.
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
from repro.store import reassign_k

R, T = 64, 32


def _hot_workload():
    def bump(vals, args):
        return vals.at[..., 0].add(1), jnp.zeros((), bool)

    return Workload(name="hot", n_read=1, n_write=1, payload_words=1,
                    branches=(bump,))


def _hot_batch(n_txns=8, rec=0):
    recs = np.full((n_txns, 1), rec)
    return make_batch(recs, recs.copy(), np.zeros(n_txns),
                      np.zeros((n_txns, 1)))


def _zipf_batch(rng, theta=0.9, ops=4):
    return gen_ycsb_batch(rng, T, R, theta=theta, mix="10rmw", ops=ops)


def _tree_equal(a, b, msg=""):
    la, ta = jax.tree.flatten(a)
    lb, tb = jax.tree.flatten(b)
    assert ta == tb, f"{msg}: tree structure"
    for x, y in zip(la, lb):
        np.testing.assert_array_equal(np.asarray(x), np.asarray(y), msg)


# ---------------------------------------------------------------------------
# 1. the masked resolve kernel == jnp reference (the spill read path)
# ---------------------------------------------------------------------------
def test_masked_resolve_matches_ref():
    rng = np.random.default_rng(3)
    B, K, D = 37, 6, 5
    begin = rng.integers(0, 50, (B, K)).astype(np.int32)
    end = begin + rng.integers(1, 30, (B, K)).astype(np.int32)
    rec = rng.integers(-1, 4, (B, K)).astype(np.int32)   # -1 = free slot
    want = rng.integers(0, 4, B).astype(np.int32)
    data = rng.integers(0, 99, (B, K, D)).astype(np.int32)
    ts = rng.integers(0, 80, B).astype(np.int32)
    v_k, f_k = ops.mvcc_resolve_masked(begin, end, rec, want, data, ts,
                                       interpret=True)
    v_r, f_r = ref.mvcc_resolve_masked_ref(begin, end, rec, want, data,
                                           jnp.asarray(ts))
    np.testing.assert_array_equal(np.asarray(v_k), np.asarray(v_r))
    np.testing.assert_array_equal(np.asarray(f_k), np.asarray(f_r))
    # an unmasked window (every slot owned by the wanted record) degrades
    # to the primary kernel — the two levels resolve identically
    rec_all = np.broadcast_to(want[:, None], (B, K)).copy()
    v_m, f_m = ops.mvcc_resolve_masked(begin, end, rec_all, want, data,
                                       ts, interpret=True)
    v_p, f_p = ops.mvcc_resolve(begin, end, data, ts, interpret=True)
    np.testing.assert_array_equal(np.asarray(v_m), np.asarray(v_p))
    np.testing.assert_array_equal(np.asarray(f_m), np.asarray(f_p))


# ---------------------------------------------------------------------------
# 2. the headline behaviour: reads that used to report found=False after
# K-ring overflow now return the REAL version via the spill path
# ---------------------------------------------------------------------------
def test_spill_recovers_pinned_hot_record():
    wl = _hot_workload()
    eng = BohmEngine(4, wl, ring_slots=2)                # spill on (default)
    bare = BohmEngine(4, wl, ring_slots=2, spill_slots=0)
    oracle = BohmEngine(4, wl, ring_slots=256, spill_slots=0)
    engines = (eng, bare, oracle)
    for e in engines:
        e.run_batch(_hot_batch())
    snaps = [e.begin_snapshot() for e in engines]
    for _ in range(3):
        for e in engines:
            e.run_batch(_hot_batch())

    reads = [e.snapshot_read(np.array([0]), s)
             for e, s in zip(engines, snaps)]
    (v, f), (vb, fb), (vo, fo) = reads
    assert bool(fo[0]) and int(vo[0, 0]) == 8            # oracle truth
    assert not bool(fb[0])                               # bare ring: lost
    assert bool(f[0])                                    # spill: recovered
    np.testing.assert_array_equal(np.asarray(v), np.asarray(vo))
    stats = eng.spill_stats()
    assert stats["spill_admitted"] >= 1
    assert stats["spill_occupancy"] >= 1


# ---------------------------------------------------------------------------
# 3. property: zipfian hot-record update stream, pinned snapshot reads
# byte-identical to the unbounded-K oracle at 1 and 2 logical shards,
# before and after gc_sweep
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("n_shards", [1, 2])
def test_spill_matches_unbounded_oracle_zipfian(n_shards):
    wl = make_ycsb(payload_words=2, ops=4)
    eng = BohmEngine(R, wl, ring_slots=2, n_shards=n_shards,
                     spill_buckets=16, spill_slots=16)
    oracle = BohmEngine(R, wl, ring_slots=512, spill_slots=0,
                        n_shards=n_shards)
    rng = np.random.default_rng(11)
    batches = [_zipf_batch(rng) for _ in range(6)]

    snaps, osnaps = [], []
    for i, batch in enumerate(batches):
        r_e, _ = eng.run_batch(batch)
        r_o, _ = oracle.run_batch(batch)
        np.testing.assert_array_equal(np.asarray(r_e), np.asarray(r_o))
        if i % 2 == 0:                       # pin every other barrier
            snaps.append(eng.begin_snapshot())
            osnaps.append(oracle.begin_snapshot())

    assert int(jnp.sum(eng.overflow_by_record())) > 0    # stream overflows

    def check():
        for s, o in zip(snaps, osnaps):
            v_e, f_e = eng.snapshot_read(np.arange(R), s)
            v_o, f_o = oracle.snapshot_read(np.arange(R), o)
            assert bool(f_o.all())           # oracle always finds
            np.testing.assert_array_equal(np.asarray(f_e),
                                          np.asarray(f_o))
            np.testing.assert_array_equal(np.asarray(v_e),
                                          np.asarray(v_o))

    check()
    eng.gc_sweep()                           # sweeps must not lose pinned
    oracle.gc_sweep()                        # history on either side
    check()
    assert eng.spill_stats()["spill_dropped"] == 0


# ---------------------------------------------------------------------------
# 4. the live/dead split (satellite fix): with NO pins, everything a
# hot record evicts is dead — zero live evictions, nothing spilled,
# while the dead counter sees the churn the old watermark test miscounted
# ---------------------------------------------------------------------------
def test_live_dead_eviction_split_no_pins():
    wl = _hot_workload()
    eng = BohmEngine(4, wl, ring_slots=2)
    for _ in range(4):
        eng.run_batch(_hot_batch())
    stats = eng.overflow_stats()
    assert stats["total_overwrites"] == 0            # live: none
    assert stats["dead_overwrites"] > 0              # dead: all the churn
    assert eng.spill_stats()["spill_occupancy"] == 0  # nothing spilled
    assert eng.spill_stats()["spill_admitted"] == 0


def test_live_dead_eviction_split_pin_bounds_spill():
    """A pin holds exactly ONE visible version per record: the live
    counter (and spill traffic) must count that version once, not the
    whole superseded history between the pin and now."""
    wl = _hot_workload()
    eng = BohmEngine(4, wl, ring_slots=2)
    eng.run_batch(_hot_batch())
    eng.begin_snapshot()
    for _ in range(5):
        eng.run_batch(_hot_batch())
    stats = eng.overflow_stats()
    assert stats["total_overwrites"] == 1            # one pin-visible
    assert stats["dead_overwrites"] > stats["total_overwrites"]
    assert eng.spill_stats()["spill_occupancy"] == 1


# ---------------------------------------------------------------------------
# 5. gc_sweep: idempotent, and a full pin release drains the spill pool
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("adaptive", [False, True])
def test_gc_sweep_idempotent_and_drains_spill(adaptive):
    wl = make_ycsb(payload_words=2, ops=4)
    eng = BohmEngine(R, wl, ring_slots=2, spill_buckets=16,
                     spill_slots=16, adaptive_k=adaptive, k_max=6)
    rng = np.random.default_rng(23)
    snaps = []
    for i in range(5):
        eng.run_batch(_zipf_batch(rng))
        snaps.append(eng.begin_snapshot())
    assert eng.spill_stats()["spill_occupancy"] > 0

    eng.gc_sweep()
    swept_once = jax.tree.map(lambda x: x, eng.store)
    eng.gc_sweep()
    _tree_equal(eng.store, swept_once, "second sweep must be a no-op")

    # release every pin: the next sweep reclaims ALL spilled versions
    for s in snaps:
        eng.release_snapshot(s)
    reclaimed = eng.gc_sweep()
    assert reclaimed > 0
    assert eng.spill_stats()["spill_occupancy"] == 0
    # drained pool == freshly initialised pool, byte for byte
    fresh = BohmEngine(R, wl, ring_slots=2, spill_buckets=16,
                       spill_slots=16, adaptive_k=adaptive, k_max=6)
    _tree_equal(eng.store.versions.spill, fresh.store.versions.spill,
                "drained spill == init")
    eng.gc_sweep()
    assert eng.spill_stats()["spill_occupancy"] == 0


# ---------------------------------------------------------------------------
# 6. adaptive-K policy: unit properties + engine integration
# ---------------------------------------------------------------------------
def test_reassign_k_policy_unit():
    pressure = np.array([9, 0, 0, 0, 2, 0, 0, 0])
    k = np.full(8, 4)
    out = reassign_k(pressure, k, k_min=1, k_max=8)
    assert out.sum() == k.sum()                      # budget preserved
    assert out.min() >= 1 and out.max() <= 8
    assert out[0] == 8                               # hottest fills first
    assert out[4] > 4                                # second-hottest grows
    assert (out[[1, 2, 3, 5, 6, 7]] <= 4).all()      # donors only shrink
    # fixpoint: a second pass with the same pressure changes nothing
    np.testing.assert_array_equal(reassign_k(pressure, out, k_min=1,
                                             k_max=8), out)
    # determinism incl. tie-breaks by record id
    np.testing.assert_array_equal(
        reassign_k(pressure, k, k_min=1, k_max=8), out)
    # no pressure -> no movement
    np.testing.assert_array_equal(
        reassign_k(np.zeros(8, int), k, k_min=1, k_max=8), k)
    with pytest.raises(ValueError):
        reassign_k(pressure, k, k_min=0, k_max=8)


def test_adaptive_k_engine_grows_hot_record():
    """A hot record under pin pressure grows its effective ring (funded
    by the stable-idle tail), the budget holds, and pinned reads stay
    correct through the grown ring + spill."""
    wl = _hot_workload()
    eng = BohmEngine(8, wl, ring_slots=4, adaptive_k=True, k_max=8,
                     spill_buckets=4, spill_slots=8)
    eng.run_batch(_hot_batch(rec=0))
    pin = eng.begin_snapshot()
    for _ in range(4):
        eng.run_batch(_hot_batch(rec=0))
        eng.gc_sweep()                       # policy runs at GC boundaries
    k = np.asarray(eng.k_by_record())
    assert k[0] > 4                          # the hot record grew
    assert k.sum() == 8 * 4                  # inside the fixed budget
    assert k.min() >= 1
    # still read-correct at the pin through the grown ring + spill
    vals, found = eng.snapshot_read(np.array([0]), pin)
    assert bool(found[0]) and int(vals[0, 0]) == 8


def _hotset_mini_batch(rng, hot_n=16, cold_n=64, n_txns=32, ops=2):
    """The benchmark's workload shape in miniature: a stable hot set, an
    active cold band, and an idle donor tail."""
    kind = rng.random((n_txns, ops))
    recs = np.where(kind < 0.5, rng.integers(0, hot_n, (n_txns, ops)),
                    rng.integers(hot_n, hot_n + cold_n, (n_txns, ops)))
    dup = recs[:, 1] == recs[:, 0]
    recs[dup, 1] = (recs[dup, 1] + 1) % (hot_n + cold_n)
    return make_batch(recs, recs.copy(), np.zeros(n_txns, np.int32),
                      np.zeros((n_txns, 1), np.int32))


@pytest.mark.parametrize("seed", [7, 42])
def test_adaptive_k_raises_found_rate_at_equal_budget(seed):
    """The acceptance shape of benchmarks/spill.py in miniature: same
    primary-slot budget, same (tiny) spill pool — adaptive K must recover
    at least as many pinned historical reads as fixed K."""
    wl = make_ycsb(payload_words=2, ops=2)

    def run(adaptive):
        rng = np.random.default_rng(seed)
        kw = dict(adaptive_k=True, k_max=16) if adaptive else {}
        e = BohmEngine(256, wl, ring_slots=4, spill_buckets=4,
                       spill_slots=2, **kw)
        pins, found = [], None
        for i in range(12):
            e.run_batch(_hotset_mini_batch(rng))
            if (i + 1) % 2 == 0:
                pins.append(e.begin_snapshot())
                while len(pins) > 2:
                    e.release_snapshot(pins.pop(0))
                e.gc_sweep()
        found = np.concatenate([
            np.asarray(e.snapshot_read(np.arange(80), p)[1])
            for p in pins])
        return float(found.mean())

    assert run(adaptive=True) >= run(adaptive=False)


# ---------------------------------------------------------------------------
# 7. saturation: a deliberately tiny spill pool may LOSE history, but a
# read is then found=False — never a stale payload
# ---------------------------------------------------------------------------
def test_spill_saturation_never_stale():
    wl = make_ycsb(payload_words=2, ops=4)
    eng = BohmEngine(R, wl, ring_slots=2, spill_buckets=1, spill_slots=2)
    oracle = BohmEngine(R, wl, ring_slots=512, spill_slots=0)
    rng = np.random.default_rng(5)
    snaps, osnaps = [], []
    for i in range(6):
        batch = _zipf_batch(rng, theta=1.1)
        eng.run_batch(batch)
        oracle.run_batch(batch)
        snaps.append(eng.begin_snapshot())
        osnaps.append(oracle.begin_snapshot())
    assert eng.spill_stats()["spill_dropped"] > 0    # it really saturated
    for s, o in zip(snaps, osnaps):
        v_e, f_e = eng.snapshot_read(np.arange(R), s)
        v_o, _ = oracle.snapshot_read(np.arange(R), o)
        f_e = np.asarray(f_e)
        np.testing.assert_array_equal(np.asarray(v_e)[f_e],
                                      np.asarray(v_o)[f_e])
        assert (np.asarray(v_e)[~f_e] == 0).all()


# ---------------------------------------------------------------------------
# 8. service: the conflict-aware scheduler over a spill-backed store is
# byte-identical to sequential run_batch — per-ticket reads, pinned
# snapshot reads through the spill path, rings after one gc_sweep
# ---------------------------------------------------------------------------
def test_service_spill_matches_sequential():
    from repro.store import unshard
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

    e1 = BohmEngine(R, wl, ring_slots=2, spill_buckets=16, spill_slots=16)
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

    for s0, s1 in zip(seq_snaps, svc_snaps):
        assert s0.ts == s1.ts
        v0, f0 = e0.snapshot_read(np.arange(R), s0)
        v1, f1 = e1.snapshot_read(np.arange(R), s1)
        np.testing.assert_array_equal(np.asarray(f0), np.asarray(f1))
        np.testing.assert_array_equal(np.asarray(v0), np.asarray(v1))

    e0.gc_sweep()
    e1.gc_sweep()
    _tree_equal(unshard(e0.store.versions), unshard(e1.store.versions),
                "rings after gc_sweep")
    np.testing.assert_array_equal(np.asarray(e0.overflow_by_record()),
                                  np.asarray(e1.overflow_by_record()))


# ---------------------------------------------------------------------------
# 9. mesh substrate: the spill path through shard_map on 4 host devices
# (subprocess — repo convention), byte-equal to the single-shard engine
# ---------------------------------------------------------------------------
_MESH_SPILL_SCRIPT = textwrap.dedent("""
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
    e_mesh = BohmEngine(R, wl, mesh=mesh, ring_slots=2,
                        spill_buckets=16, spill_slots=16)
    e_one = BohmEngine(R, wl, ring_slots=2, spill_buckets=64,
                       spill_slots=16)
    assert e_mesh.n_shards == 4
    assert e_mesh.store.versions.spill is not None
    rng = np.random.default_rng(13)
    snap_m = snap_o = None
    for i in range(5):
        batch = gen_ycsb_batch(rng, T, R, theta=0.9, ops=4)
        r_m, _ = e_mesh.run_batch(batch)
        r_o, _ = e_one.run_batch(batch)
        np.testing.assert_array_equal(np.asarray(r_m), np.asarray(r_o))
        if i == 0:
            snap_m = e_mesh.begin_snapshot()
            snap_o = e_one.begin_snapshot()
    # the stream overflowed the K=2 rings...
    assert int(jnp.sum(e_mesh.overflow_by_record())) > 0
    v_m, f_m = e_mesh.snapshot_read(np.arange(R), snap_m)
    v_o, f_o = e_one.snapshot_read(np.arange(R), snap_o)
    # ...and the mesh spill path still answers every pinned read
    np.testing.assert_array_equal(np.asarray(f_m), np.asarray(f_o))
    np.testing.assert_array_equal(np.asarray(v_m), np.asarray(v_o))
    assert bool(f_m.all())
    assert e_mesh.spill_stats()["spill_occupancy"] > 0
    e_mesh.gc_sweep()
    v_m2, f_m2 = e_mesh.snapshot_read(np.arange(R), snap_m)
    np.testing.assert_array_equal(np.asarray(v_m2), np.asarray(v_m))
    np.testing.assert_array_equal(np.asarray(f_m2), np.asarray(f_m))
    print("MESH_SPILL_OK")
""")


def test_spill_mesh_substrate():
    root = Path(__file__).resolve().parents[1]
    env = dict(os.environ)
    env["PYTHONPATH"] = str(root / "src")
    env.pop("XLA_FLAGS", None)
    out = subprocess.run([sys.executable, "-c", _MESH_SPILL_SCRIPT],
                         capture_output=True, text=True, env=env,
                         cwd=str(root), timeout=600)
    assert out.returncode == 0, out.stderr[-2000:]
    assert "MESH_SPILL_OK" in out.stdout
